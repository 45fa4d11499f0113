"""Window scheduling for the serving engine (ISSUE 9 engine split).

The engine split's scheduling third: every "what should the next device
dispatch be" decision — how many steps each lane may still run and the
decode-window size (K) the lane with the most left can fill, speculative-
verify eligibility and the acceptance-EWMA gate, and the admission-can-
proceed check that shrinks windows when a queued request could actually land.
Pure host arithmetic over the engine's scheduling state (host length
mirrors, budgets, in-flight step counts); it never touches device arrays
or dispatches anything itself, so it is identical on one chip and on a
sharded submesh.

The scheduler reads the engine directly (they are one subsystem split by
responsibility, not an RPC boundary) and records WHY it chose a window in
``engine._pick_reason`` — the flight recorder's "why was K small" answer.
"""

from __future__ import annotations

import numpy as np


class WindowScheduler:
    """Scheduling brain for one :class:`~tpu9.serving.engine.
    InferenceEngine` — constructed by, and reading, that engine."""

    def __init__(self, engine):
        self.engine = engine

    def admission_can_proceed(self) -> bool:
        """True only when a waiting request could ACTUALLY be admitted
        right now (free slot + KV room for the FIFO head) — the only case
        where shrinking the next window to K=1 buys admission latency.
        The old check (`not queue.empty()`) collapsed throughput to
        single-step windows under saturation, when the queued head could
        not be admitted anyway (batch full / pool exhausted) and small
        windows bought nothing."""
        e = self.engine
        if e.active.all():
            return False
        head = None
        if e.paged and e._wait_room:
            head = e._wait_room[0]
        else:
            q = getattr(e._queue, "_queue", None)    # deque peek, no pop
            if q:
                head = q[0]
        return head is not None and e._room_for(head)

    def lane_steps(self) -> np.ndarray:
        """int32 [max_batch]: the decode steps each lane may still be GIVEN —
        what is left of its ``max_new_tokens`` budget and of its cache room,
        whichever is less, without the steps it already has in flight (the
        steady-state overlap window, a window interleaved inside an
        admission); 0 for an idle lane. The ONE per-lane number of the
        window paths: the decode program takes it as it is and stops the
        lane itself after that many steps (``GraphFactory.build_decode``),
        so budget and room are hard on the device and neither bounds the
        window's size."""
        e = self.engine
        left = np.zeros((e.ecfg.max_batch,), np.int32)
        for slot in np.flatnonzero(e.active):
            req = e.slot_req[slot]
            budget = req.max_new_tokens - len(req.generated)
            room = e.ecfg.max_seq_len - 1 - int(e._host_len[slot])
            left[slot] = max(0, min(budget, room)
                             - int(e._lane_inflight[slot]))
        return left

    def pick_steps(self, left=None) -> int:
        """The decode window for lanes with ``left`` steps to run
        (:meth:`lane_steps`): the largest bucket that the lane with the MOST
        left can fill — a lane with less is parked by the program when its
        number is up, so one nearly-done stream costs the others nothing,
        and no window runs a step in which every lane is parked — else the
        smallest bucket. Admission latency wins when an admission could
        actually proceed: the smallest bucket."""
        e = self.engine
        if self.admission_can_proceed():
            # shrink to the smallest window so the waiting head admits
            # sooner — the flight recorder's "why was K small" answer
            e._pick_reason = "admission"
            return e.ecfg.decode_steps[0]
        if left is None:
            left = self.lane_steps()
        k = self.bucket_within(int(left.max(initial=0))) \
            or e.ecfg.decode_steps[0]
        # "budget": the lane with the most left could not fill the largest
        e._pick_reason = ("max" if k >= max(e.ecfg.decode_steps)
                          else "budget")
        return k

    def bucket_within(self, limit: int) -> int:
        """The largest decode bucket of at most ``limit`` steps; 0: none."""
        return max((k for k in self.engine.ecfg.decode_steps if k <= limit),
                   default=0)

    def spec_room_len(self) -> int:
        """Largest spec bucket the batch has ROOM for, or 0 when
        speculation is off or structurally blocked (imminent admission,
        cache room, exhausted budgets). Slots near their cache limit veto
        the bucket — a dense write past max_seq_len would clamp backwards
        over valid KV."""
        e = self.engine
        if not e._spec_lens:
            return 0
        if self.admission_can_proceed():
            return 0              # admission latency wins, as for K
        min_room = e.ecfg.max_seq_len
        max_remaining = 0
        any_active = False
        for slot in range(e.ecfg.max_batch):
            req = e.slot_req[slot]
            if req is None or not e.active[slot]:
                continue
            any_active = True
            min_room = min(min_room,
                           e.ecfg.max_seq_len - 1
                           - int(e._host_len[slot])
                           - e._inflight_steps)
            max_remaining = max(max_remaining,
                                req.max_new_tokens - len(req.generated)
                                - e._inflight_steps)
        if not any_active or max_remaining < 2:
            return 0
        for s in sorted(e._spec_lens, reverse=True):
            if s + 1 <= min_room:
                return s
        return 0

    def spec_gate(self, s: int) -> int:
        """Acceptance-EWMA gate: speculate only when the mean EFFECTIVE
        acceptance over active slots clears the floor. Effective means a
        slot with nothing to propose RIGHT NOW contributes 0 — a verify
        window hands it ~1 token where a classic K-step window hands it
        K, so idle proposers must drag the decision toward classic (their
        optimistic starting EWMA must not). Below the floor speculation
        auto-disables, except one probe window every ``spec_probe_every``
        classic windows — which is how a stream that turns repetitive
        later gets speculation back."""
        e = self.engine
        total = 0.0
        n = 0
        for slot in range(e.ecfg.max_batch):
            if e.slot_req[slot] is None or not e.active[slot]:
                continue
            n += 1
            st = e._spec_slots[slot]
            if st is not None and st.proposer.propose(1):
                total += st.ewma
        if n == 0:
            return 0
        mean = total / n
        if mean >= e.ecfg.spec_min_accept:
            e._spec_disabled_windows = 0
            return s
        e._spec_disabled_windows += 1
        pe = e.ecfg.spec_probe_every
        if pe > 0 and e._spec_disabled_windows >= pe:
            e._spec_disabled_windows = 0
            return s
        return 0

    def downpage_quota(self) -> int:
        """How many prefix-cache entries the current window boundary
        should down-page to host DRAM (ISSUE 20): 0 unless the free list
        has sunk under the low-water mark — the point where the NEXT
        burst of admissions would push ``evict_for_space`` into
        destroying prefixes the host tier could have kept. Bounded per
        boundary (each down-page is one device gather) so a pressure
        spike amortizes over windows instead of stalling one."""
        e = self.engine
        pool = e.pool
        if pool is None or not pool.tiered:
            return 0
        alloc = pool.allocator
        # low water: an eighth of the pool, or at least one admission
        # chunk's worth of blocks — below it, eviction is imminent
        chunk_blocks = max(1, e._chunk // e.ecfg.kv_block_size)
        low = max(2 * chunk_blocks, alloc.n_blocks // 8)
        if alloc.free_count >= low:
            return 0
        return 2
