"""LLM inference engine: continuous batching over jitted prefill/decode.

TPU-first rationale: the engine compiles a small fixed set of graphs per
shape bucket — ``prefill(tokens[1, Tpad])``, ``decode(tokens[B,1])`` windows
(k steps per host sync) and, with speculation on, ``verify(tokens[B,1+s])``
(prompt-lookup drafts checked in ONE batched forward, ISSUE 5) — and keeps
the KV cache as a persistent on-device buffer donated through every step, so
steady-state decoding is one fused XLA computation per WINDOW across the
whole batch with zero host↔device traffic except the sampled ids.

Slots: fixed max_batch decode lanes. New requests prefill (bucketed lengths to
bound compile count), then join the decode batch at their slot index. This is
the same admission shape the reference's LLM-aware pod router assumes
(``pkg/abstractions/pod/llm.go`` token-pressure/active-streams), which the
gateway reads from the engine's ``stats()``.

Decomposition (ISSUE 9): this module is the serve LOOP — admission,
window dispatch/fan-out, request lifecycle, observability. The three
split-off responsibilities live next door with an explicit boundary
(BND001 contracts in ``tpu9/analysis/boundaries.toml``):

- :mod:`tpu9.serving.graphs`   — every traced/compiled XLA computation
- :mod:`tpu9.serving.schedule` — window-size / spec-gate decisions
- :mod:`tpu9.serving.kvpool`   — paged-pool sizing + block bookkeeping
- :mod:`tpu9.serving.shard`    — the sharding POLICY all device placement
  goes through: ``topology 1x1`` is the identity (this engine, verbatim,
  bit-identical graphs); ``tp×fsdp`` shards weights and the KV pool's
  head axis across a submesh while everything host-side here stays
  topology-oblivious (block ids are global; only resident layout shards).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import DecoderConfig
from ..observability.metrics import Metrics
from ..observability.trace import PhaseTotals, phase, tracer
from ..ops.sampling import sample_logits
from ..utils.aio import reap
from .flight import maybe as flight_maybe
from .graphs import GraphFactory
from .schedule import WindowScheduler

Params = dict[str, Any]

# deadline-expiry error prefix (ISSUE 15). This string is a WIRE contract:
# the llm runner maps it to 504 and the gateway's failover classifier
# treats it as final (the budget is spent — retrying would burn chips on
# an answer the client stopped waiting for). Keep in sync with
# tpu9.gateway.survival.DEADLINE_ERROR (the boundary map forbids a
# shared import in either direction).
DEADLINE_ERROR = "deadline_exceeded"


def abstract_params(tree: Any) -> Any:
    """Pytree of arrays (or ShapeDtypeStructs) → matching
    ``jax.ShapeDtypeStruct`` tree. The compile-ahead contract: everything
    :meth:`InferenceEngine.precompile` needs from the weights is this."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


@dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8
    max_seq_len: int = 2048
    prefill_buckets: tuple = (128, 512, 2048)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: int = -1              # -1 disables EOS stopping
    # decode-window buckets: K steps run on-device (lax.scan) per host
    # sync. Each host↔device round-trip costs wall-clock, so the loop
    # amortizes it over K tokens; K drops to 1 whenever requests wait for
    # admission.
    decode_steps: tuple = (1, 4, 16)
    # ---- paged KV (VERDICT r03 #5) ----
    # block size of the shared KV pool; 0 = legacy dense [B, S] cache
    kv_block_size: int = 0
    # pool size in blocks; 0 = auto (max_batch * max_seq/block — dense
    # parity). Set lower to BOUND KV memory: admission then reserves
    # against it and queues when full.
    kv_pool_blocks: int = 0
    # chunked-prefill chunk length (paged mode); long prompts compile
    # ONE (C, S) graph instead of a full-length bucket. 0 = auto (=
    # smallest prefill bucket).
    prefill_chunk: int = 0
    # pool blocks the engine-level prefix cache may hold for KV reuse
    # across requests sharing a prompt prefix; 0 disables
    prefix_cache_blocks: int = 0
    # "int8" stores the paged KV pool as int8 with per-(position, head)
    # f32 absmax scales alongside it (ISSUE 6): writes quantize, the
    # decode/verify attention dequantizes in-kernel, and with
    # kv_pool_blocks=0 (auto) the pool is sized to the SAME HBM bytes the
    # bf16 pool would have used — i.e. ~2x the blocks, which is directly
    # more admission headroom (reservations, router kv_blocks signal).
    # Requires the paged engine ("" = full-precision pool).
    kv_quant: str = ""
    # chunks per fused admission dispatch (VERDICT r04 #6): a group of G
    # chunks runs as ONE forward over its G·C contiguous positions (the
    # weights are read once per group) with the block splice fused in,
    # and the serve loop interleaves a decode window between groups so a
    # long admission doesn't starve the decode batch. The wide forward's
    # temporaries are G times a chunk's. 1 = every chunk through the
    # single-chunk graphs (still no per-chunk sync)
    admit_group_chunks: int = 4
    # ---- speculative decoding (ISSUE 5) ----
    # max draft tokens per verify window (prompt-lookup n-gram drafts,
    # tpu9/serving/spec.py); 0 disables speculation. One batched forward
    # verifies [B, 1+spec_len] positions — in the bandwidth-bound decode
    # regime that pass costs ~one decode step of HBM traffic, so every
    # accepted draft token is nearly free.
    spec_len: int = 0
    # acceptance-EWMA floor (mean EFFECTIVE acceptance over active slots,
    # non-proposing slots counting 0): below it the serve loop falls back
    # to classic windowed decode so adversarial prompts never regress
    # past a probe's worth of wasted verify compute. The measured CPU
    # break-even for spec_len=8 is ~0.25 (verify ≈ 2.6-3 decode steps);
    # the floor sits above it so the gate only admits windows that WIN,
    # not ones that tread water while paying scheduling overhead. On TPU
    # the bandwidth-bound verify is ~1 step, so the floor is conservative
    spec_min_accept: float = 0.35
    # after auto-disable, force one speculative window every N classic
    # windows regardless of the EWMA. 0 (default) disables forced probes:
    # classic windows SHADOW-SCORE the proposer against their own output
    # (see _Window.shadow), so the EWMA recovers for free the moment a
    # stream turns repetitive — blind probe windows would only burn
    # verify compute re-learning what the shadows already measured
    spec_probe_every: int = 0
    # ---- KV tiering (ISSUE 20) ----
    # host-DRAM second tier for the paged KV pool, in MB; 0 disables
    # tiering entirely (the pool is bit-identical to the untiered one).
    # TPU9_KV_HOST_POOL_MB overrides at engine construction, and the
    # TPU9_KV_TIER master gate can force tiering off regardless.
    kv_host_pool_mb: int = 0
    # ---- observability (ISSUE 8) ----
    # flight-recorder ring capacity, in records (one per dispatched window
    # or admission — never per token). 0 disables the recorder entirely;
    # the hot path then pays one `is not None` check per window.
    flight_cap: int = 256


def refuse_unbuilt_with_summaries(cfg, ecfg: "EngineConfig",
                                  topo: dict) -> None:
    """What is not built for a cache of window summaries (``DecoderConfig.
    attn_window``): refused when the engine is made, each with its reason,
    and not half-built."""
    w, entries = cfg.attn_window, cfg.window_entries
    chunk = ecfg.prefill_chunk or min(ecfg.prefill_buckets)

    def refuse(what: str, why: str):
        raise ValueError(f"attn_window={w} with {what}: {why}")

    if ecfg.kv_block_size <= 0:
        refuse("kv_block_size=0 (the dense cache)",
               "a closed window's summaries replace its tokens page by "
               "page; only the paged pool has pages, and the dense decode "
               "cache has no program that summarises")
    if ecfg.kv_block_size != entries:
        refuse(f"kv_block_size={ecfg.kv_block_size}",
               f"a closed window's {entries} summaries (attn_window / "
               "attn_chunk) must be exactly one page, so that the pool has "
               "one page shape and a window's first page can take them")
    if w % chunk:
        refuse(f"prefill_chunk={chunk}",
               "a chunk that straddles two windows would need the first "
               "one summarised in the middle of its forward pass")
    group = max(1, ecfg.admit_group_chunks) * chunk
    if group <= ecfg.max_seq_len and w % group:
        refuse(f"admit_group_chunks={ecfg.admit_group_chunks} x "
               f"prefill_chunk={chunk}",
               "an admission group is one forward pass and may not "
               "straddle two windows either")
    if ecfg.prefix_cache_blocks > 0:
        refuse(f"prefix_cache_blocks={ecfg.prefix_cache_blocks}",
               "the pages of an open window are rewritten in place when it "
               "closes, so a page shared by two sequences would be "
               "overwritten by one of them; sharing whole closed windows' "
               "summary pages is not built")
    if ecfg.kv_quant:
        refuse(f"kv_quant={ecfg.kv_quant!r}",
               "the summarise reads and writes the pool in the model's "
               "type and knows no scale planes")
    if topo["tp"] > 1:
        refuse(f"tp={topo['tp']}",
               "the summarise loop is one chip's program: under a mesh it "
               "would have to run per chip on its own heads, which is not "
               "built")
    if ecfg.spec_len > 0:
        refuse(f"spec_len={ecfg.spec_len} (verify)",
               "a verify window writes several positions a lane at once "
               "and may cross a window's end inside one forward pass; "
               "rejected drafts would also have been summarised")
    if ecfg.kv_host_pool_mb > 0:
        refuse(f"kv_host_pool_mb={ecfg.kv_host_pool_mb}",
               "the host tier and the kvwire format address blocks by "
               "token position, and this pool is addressed by entry")


def refuse_unbuilt_with_lane_state(cfg, ecfg: "EngineConfig",
                                   topo: dict) -> None:
    """What is not built for a decoder whose layers are not all plain
    attention — a layer pattern stated by rule (``layer_group``: delta-rule
    layers, whose state is kept by LANE, closed by latent attention over one
    row a token) or as a list (``layer_pattern``: state-space layers or gated
    short convolutions, state a lane too, around plain attention over
    per-head rows): refused when the engine is made, each with its reason,
    and not half-built.

    Two sorts of refusal, each option asked once. The STATE's — asked of
    ``cfg.lane_state`` (does any layer keep state a lane), not of the
    pattern: the state's reasons for the dense cache, verify, a mesh and the
    host tier, and the prefix cache — a hit starts a sequence behind cached
    pages, and those layers need their state as of that page's last row. The
    kinds are told apart by what such a snapshot keeps (``SNAPSHOT``): a
    matrix a head that every token rewrites (``"kda"``, ``"ssm"``: megabytes
    a boundary, which nothing keeps — refused), or the last rows of a product
    the prefill has in hand (``"conv"``: kilobytes a layer, which the pool
    keeps a BLOCK, ``kvstate.BLOCK_TAIL`` — built, for a decoder whose only
    state a lane is that). A pattern with no such layer keeps no such state,
    and its latent pages are shared by the prefix cache like any paged rows.
    The ROWS' — for every ``layer_group``, because a cache row is a latent:
    the dense cache, verify (no program attends a window of several
    positions over latents), a mesh, ``kv_quant``, the host tier and kvwire
    (they address per-head ``k`` / ``v`` planes; no sharding rule and no
    wire format names a latent row). Per-head rows beside state are the
    plain pool's, and only the state refuses. A list's expert layers beside
    state are told which experts they hold and take the dropless held /
    sorted forms on one device: every refusal here stands for them too (no
    mesh, no int8 stacks, no verify). Nothing in the engine preempts a
    running lane, so there is no path that drops a lane's state without
    re-prefilling it; one that is added has to snapshot or re-prefill
    (ROADMAP R6)."""
    latent, state = cfg.latent_rows, cfg.lane_state
    layers = " and ".join(SNAPSHOT[kind] for kind in state)
    label = cfg.pattern_label if latent else \
        f"{cfg.pattern_label} with state a lane ({'/'.join(state)})"

    def refuse(what: str, why: str):
        raise ValueError(f"{label} with {what}: {why}")

    if ecfg.kv_block_size <= 0:
        refuse("kv_block_size=0 (the dense cache)",
               " and ".join(
                   ["the latent cache is built as a paged pool"] * latent
                   + ["the dense prefill buckets carry no state into a lane "
                      "(it is carried chunk to chunk through the paged "
                      "engine's batch-1 scratch and spliced in at "
                      "admission)"] * bool(state)))
    if ecfg.prefix_cache_blocks > 0 and state and state != ("conv",):
        from ..models import kvstate
        each = kvstate.lane_bytes(cfg)
        refuse(f"prefix_cache_blocks={ecfg.prefix_cache_blocks}",
               "a hit would start a sequence behind cached pages, and the "
               f"layers that keep {layers} would need a snapshot of it at "
               f"that block boundary — {each:,} B a boundary here, beside "
               f"{kvstate.block_bytes(cfg, ecfg.kv_block_size):,} B of rows "
               "a block — which nothing keeps")
    if state == ("conv",) and 0 < ecfg.kv_block_size < cfg.conv_taps - 1:
        refuse(f"kv_block_size={ecfg.kv_block_size}",
               f"a page's tail is its own last {cfg.conv_taps - 1} rows: a "
               "page is at least that long")
    if ecfg.spec_len > 0:
        refuse(f"spec_len={ecfg.spec_len} (verify)",
               f"a verify window advances {layers} over its "
               "drafts, and a rejected draft has then already changed it: "
               "there is no state to roll back to" if state else
               "a verify window attends several positions a lane at once, "
               "and latent attention is built for a decode step's one "
               "(absorbed) and for a chunk over the batch-1 scratch: no "
               "program attends a window over the pool's latent rows")
    if topo["tp"] > 1 or topo.get("fsdp", 1) > 1 \
            or topo.get("n_chips", 1) > 1:
        refuse(f"a mesh ({topo})", {
            (True, True):
               "the state a lane, the latent pool and the held experts are "
               "one chip's: no sharding rule names them, and the grouped "
               "expert kernel is not partitioned",
            (True, False):
               "a latent row is one row for all heads, so the pool has no "
               "head axis to shard; no sharding rule names the latent "
               "pool or the held experts, and the latent and expert "
               "kernels are not partitioned",
            (False, True):
               "the state a lane is one chip's: no sharding rule names it "
               "(which axis of [planes, lanes, ...] a mesh shards), and "
               "the step kernel is not partitioned"
               + ("; a list's held experts take the dropless kernels on one "
                  "device alone" if cfg.moe_routed else ""),
        }[latent, bool(state)])
    if ecfg.kv_quant:
        refuse(f"kv_quant={ecfg.kv_quant!r}",
               "the latent rows are read as they are written, in the "
               "model's type; no scale planes are kept for them" if latent
               else
               "a short convolution's tail is the model's type and the "
               "attention rows lie two narrow heads to a cache row, which "
               "would share an int8 pool's one scale a (token, head); never "
               "run against the reference" if state == ("conv",) else
               "the state is float32 by the configuration and most of what "
               "a lane keeps; an int8 pool of the few attention planes "
               "beside it was never run against the reference")
    if ecfg.kv_host_pool_mb > 0:
        refuse(f"kv_host_pool_mb={ecfg.kv_host_pool_mb}",
               "the host tier and the kvwire format ship the pool's rows "
               "and no state a lane or a block: a prefix paged back in "
               f"would attend its rows with zeroed layers of {layers}"
               if state else
               "the host tier and the kvwire format ship per-head key and "
               "value planes by their names and widths; a latent row and "
               "its rotated key have no place in either format")


# what a snapshot of each kind of state a lane keeps, in words, for the
# refusals above (bytes are ``kvstate.lane_bytes``')
SNAPSHOT = {
    "kda": "a float32 matrix a head (the delta rule's) and its short "
           "convolution's tail",
    "ssm": "a float32 matrix a head (the state-space recurrence's) and its "
           "short convolution's tail",
    "conv": "the last rows a short convolution convolved",
}


@dataclass
class _Window:
    """One dispatched decode/verify window whose host fan-out is deferred:
    the device arrays are fetched later (one transfer per drain) so host
    work overlaps device compute. ``mask``/``reqs`` snapshot the active
    set AT DISPATCH — a window must deliver tokens only to the exact
    request that occupied the slot when it was dispatched (a slot retired
    and re-admitted while the window was in flight gets nothing)."""
    kind: str                 # "decode" | "verify"
    k: int                    # device steps (decode k, or 1 + spec_len)
    toks: Any                 # device [k, B] (decode) / [B, k] (verify)
    mask: Any                 # np active snapshot at dispatch
    reqs: tuple               # slot_req snapshot at dispatch
    steps: Any                # np int32 [B]: the steps each lane was given
    #                           (decode: the program parks it after them; a
    #                           verify window: ``k`` for every lane in it)
    parked: int = 0           # lane-steps its lanes sit out parked (decode)
    n_acc: Any = None         # device [B] (verify): accepted drafts/slot
    exits: Any = None         # device [k, B, 2] (decode, looped decoder):
    #                           the pass whose state the head read and the
    #                           passes the device ran, per token
    picks: Any = None         # device [k, B, expert layers, top_k] (decode,
    #                           layer pattern): the experts each lane's
    #                           token chose at each step
    spec_len: int = 0
    n_real: Any = None        # np [B] (verify): real (non-pad) drafts
    # observability (ISSUE 8): monotonic/wall anchor pair captured at
    # dispatch (durations from monotonic, merge timelines from wall), why
    # this K was picked, allocator snapshot at dispatch, and the host
    # fan-out outcome (tokens delivered per live slot) filled in during
    # processing — everything the flight record and the per-request
    # decode span need, with zero extra device syncs
    t_mono: float = 0.0
    t_wall: float = 0.0
    pick: str = ""
    kv_snap: tuple = ()       # (used, free, reserved) at dispatch (paged)
    delivered: Any = None     # {slot: tokens delivered} (host processing)
    spec_stats: tuple = ()    # (proposed, accepted) (verify processing)
    # a token's gap (ISSUE 57): the admission clock at dispatch, and at the
    # fan-out the time since the fan-out before it, the part of that the
    # admission clock moved by, and whether no admission touched the
    # window (``_obs_gap``)
    gap_clock0: float = 0.0
    period_s: float = 0.0
    admit_s: float = 0.0
    clean: bool = False


@dataclass
class _Request:
    request_id: str
    prompt: list[int]
    max_new_tokens: int
    slot: int = -1
    generated: list[int] = field(default_factory=list)
    done: asyncio.Event = field(default_factory=asyncio.Event)
    queue: Optional[asyncio.Queue] = None   # set for streaming requests
    error: str = ""
    cancelled: bool = False                 # client abandoned the request
    # request deadline (ISSUE 15): monotonic stamp past which the request
    # must not be prefilled and a mid-decode slot is retired (0 = none)
    deadline_mono: float = 0.0
    # observability (ISSUE 8): remote trace context (trace_id, parent
    # span id) carried across the runner RPC boundary; span is the
    # engine.request span opened at admission under that parent
    trace: Optional[tuple] = None
    span: Any = None
    span_id: str = ""    # survives _obs_done so the window that RETIRES a
    #                      request can still parent its decode span
    t_enqueue_mono: float = 0.0
    t_enqueue_wall: float = 0.0
    t_admit_end_mono: float = 0.0           # admission (prefill) dispatched
    t_first_mono: float = 0.0               # first token delivered
    # the runner's leg of a streamed request (ISSUE 41, `note_ingest`):
    # the handler's (wall, monotonic) anchor at its first line, and the
    # monotonic stamp at which the response's headers were written
    ingest_anchor: tuple = ()
    t_ready_mono: float = 0.0
    t_done_mono: float = 0.0                # terminal answer given
    admit_cached: int = 0                   # prefix-cache tokens reused
    admit_chunks: int = 0                   # prefill chunks dispatched
    # the prompt's block-aligned prefix keys (``PrefixCache.walk``), made
    # once at the admission's lookup and taken again by its insert
    prefix_keys: Optional[list] = None
    # the ONE engine.decode span of a traced request (ISSUE 24), summed
    # over its windows and recorded when it retires: the wall/monotonic
    # anchors of its first window's dispatch, and the span's attributes
    dec_anchor: tuple = ()
    dec: Optional[dict] = None
    # a stream's own gap (ISSUE 57): the marks (``_gap_marks``: stamp,
    # admission clock, admissions begun) at its first token and at its last
    # delivery — the one tuple a window makes, shared by every lane it
    # delivered to — and the largest period between two of its deliveries;
    # the runner's first write (``note_first_write``)
    gap_first: tuple = ()
    gap_last: tuple = ()
    gap_max: float = 0.0
    t_first_write_mono: float = 0.0
    # a layer pattern's expert layers (``routed_experts``): the experts
    # every routed position chose, [n, expert layers, top_k] a prefill
    # dispatch (device arrays with their real rows until the first token is
    # delivered) and a decode window; None where no layer says
    routed: Optional[list] = None


class InferenceEngine:
    """Continuous-batching engine around a decoder model."""

    def __init__(self, params: Params, cfg: DecoderConfig,
                 engine_cfg: EngineConfig = EngineConfig(),
                 policy=None):
        self.cfg = cfg
        self.ecfg = engine_cfg
        # sharding policy (ISSUE 9): ALL device placement below routes
        # through it. None → the single-device identity policy, which
        # makes this constructor byte-for-byte the pre-split engine.
        if policy is None:
            from .shard.policy import SingleDevicePolicy
            policy = SingleDevicePolicy()
        self.policy = policy
        # weights route through the policy HERE, not just in load_engine —
        # a mesh engine handed raw host params would otherwise serve
        # replicated weights (all the HBM, none of the sharding) the first
        # time XLA implicitly places them. Identity for 1x1; a no-op
        # device_put for already-placed trees. Compile-ahead constructs
        # with abstract ShapeDtypeStruct trees that cannot be placed —
        # bind_params places the real arrays later.
        leaves = jax.tree_util.tree_leaves(params)
        if leaves and not any(isinstance(x, jax.ShapeDtypeStruct)
                              for x in leaves):
            params = policy.place_params(params)
        self.params = params
        topo = policy.describe()
        if topo["tp"] > 1 and cfg.n_kv_heads % topo["tp"]:
            # fit_spec would silently REPLICATE the KV head axis (all the
            # HBM cost, none of the capacity win) while feasibility priced
            # the gcd shard — the exact OOM the deploy gate exists to
            # prevent. The planner only emits dividing tp; an explicit
            # override that doesn't divide must fail loudly at bind time.
            raise ValueError(
                f"topology tp={topo['tp']} does not divide n_kv_heads="
                f"{cfg.n_kv_heads} — the paged-KV head axis cannot shard "
                "evenly. Use a tp that divides the KV heads (put excess "
                "chips on fsdp, e.g. 'tp=2,fsdp=2') or topology='auto'")
        b, s = engine_cfg.max_batch, engine_cfg.max_seq_len
        self.paged = engine_cfg.kv_block_size > 0
        if cfg.attn_window:
            refuse_unbuilt_with_summaries(cfg, engine_cfg, topo)
        if not cfg.uniform:
            refuse_unbuilt_with_lane_state(cfg, engine_cfg, topo)
            from ..ops.quant import is_quantized_entry
            if any(is_quantized_entry(leaf) for leaf in
                   jax.tree_util.tree_leaves(
                       params, is_leaf=is_quantized_entry)):
                raise ValueError(
                    f"{cfg.pattern_label} with int8 weights: " + (
                        "the pattern's layers and the held experts' einsum "
                        "are built for the model's own type"
                        if cfg.latent_rows else
                        "the listed mixers' projections (and a list's "
                        "expert kernels, which read the stacks as they are "
                        "stored) are built for the model's own type"))
        from ..ops.quant import validate_quant_mode
        _kvq = validate_quant_mode(engine_cfg.kv_quant, "kv_quant")
        if _kvq and _kvq != "int8":
            # a mode added to SUPPORTED_MODES but not wired here must
            # fail, not silently serve a full-precision pool the caller
            # sized admission/HBM around
            raise NotImplementedError(
                f"kv_quant mode {_kvq!r} is not wired into the engine")
        self.kv_quant = _kvq == "int8"
        if self.kv_quant and not self.paged:
            raise ValueError("kv_quant='int8' requires the paged engine "
                             "(kv_block_size > 0)")
        # imported here, as ``stats`` does, and not at the module's head:
        # with the import there a one-chip runner's bring-up and its first
        # foreign program (the benchmark's reference) read 1.5-9 s longer
        # on the chip, run after run, and with it here they read the
        # parent's (PR 51, PERF.md §6: found by bisection, the import-time
        # effect itself not found)
        from ..models import kvstate
        if self.paged:
            from .kvpool import KvPool
            bs = engine_cfg.kv_block_size
            if s % bs:
                raise ValueError(f"max_seq_len {s} % kv_block_size {bs}")
            chunk = engine_cfg.prefill_chunk \
                or min(engine_cfg.prefill_buckets)
            if chunk % bs:
                # a chunk smaller than a block would make the splice a
                # silent no-op (nb = chunk//bs = 0) and every token would
                # decode against zero-filled prompt KV
                raise ValueError(
                    f"prefill_chunk {chunk} must be a multiple of "
                    f"kv_block_size {bs}")
            if s % chunk:
                # with S % C != 0 the final chunk of a long prompt starts
                # at an offset where offset + C > S; dynamic_update_slice
                # CLAMPS the write start backwards, silently overwriting
                # valid prefix KV (advisor r04). Reject loudly instead.
                raise ValueError(
                    f"max_seq_len {s} must be a multiple of "
                    f"prefill_chunk {chunk}")
            self._chunk = chunk     # the validated value IS the used value
            # pool sizing + trash-block + slot/block bookkeeping: the
            # split-off KV-pool manager (serving.kvpool). The aliases
            # below are the SAME objects, kept so the admission/retire
            # paths (and tests/bench) read the state where it always was.
            # host-DRAM tier (ISSUE 20): EngineConfig field, env
            # override, master gate — all resolved here so 0 MB keeps
            # the pool bit-identical to the untiered build
            from ..config import env_kv_host_pool_mb, env_kv_tier_on
            host_mb = env_kv_host_pool_mb(engine_cfg.kv_host_pool_mb)
            if not env_kv_tier_on() or engine_cfg.prefix_cache_blocks <= 0:
                host_mb = 0
            self.pool = KvPool(cfg, engine_cfg, self.kv_quant, policy,
                               host_pool_mb=host_mb)
            self.kv_cache = self.pool.init_arrays()
            self.allocator = self.pool.allocator
            self.prefix_cache = self.pool.prefix_cache
            self._slot_blocks = self.pool.slot_blocks
            self._slot_reserved = self.pool.slot_reserved
            self._table_np = self.pool.table_np
            self._trash_block = self.pool.trash_block
            self._mb = self.pool.mb
            # batch-1 dense scratch the chunked prefill writes through
            # before splicing into pool blocks — ONE lane, not B of them
            # (a row a cache ENTRY: ``max_seq_len`` for plain attention)
            from .paged_kv import scratch_len
            self._scratch = policy.place_kv(kvstate.init_kv_cache(
                cfg, 1, scratch_len(cfg, s, chunk), block=bs))
        else:
            self.pool = None
            self.kv_cache = policy.place_kv(
                kvstate.init_kv_cache(cfg, b, s))
            self.allocator = None
            self.prefix_cache = None
        # every traced/compiled graph lives in the factory (serving.graphs)
        self.graphs = GraphFactory(cfg, engine_cfg, policy,
                                   chunk=self._chunk if self.paged else 0,
                                   kv_quant=self.kv_quant)
        self.scheduler = WindowScheduler(self)
        self._buckets = sorted({min(bk, s)
                                for bk in engine_cfg.prefill_buckets})
        self.cache_len = jnp.zeros((b,), jnp.int32)     # valid prefix per slot
        self.active = np.zeros((b,), dtype=bool)
        self.slot_req: list[Optional[_Request]] = [None] * b
        self.last_token = jnp.zeros((b, 1), jnp.int32)
        self._rng = jax.random.PRNGKey(0)
        self._queue: asyncio.Queue[_Request] = asyncio.Queue()
        self._loop_task: Optional[asyncio.Task] = None
        self._dead_reason: Optional[str] = None   # loop died: fail fast
        self._admitting: Optional[_Request] = None
        # paged admission parks over-budget requests here; dense mode
        # keeps it empty (shared so failure fan-out/cancel need no mode
        # branches)
        self._wait_room: list[_Request] = []
        # host-tier up-pages in flight, keyed by prefix key: concurrent
        # admissions hitting the same host entry await the first up-page
        # instead of double-filling fresh blocks (ISSUE 20)
        self._uppage_inflight: dict = {}
        # the compiled-graph cache lives in the factory; alias for the
        # bench/diagnostic surface that predates the split
        self._compiled = self.graphs.compiled
        self._host_len = np.zeros((b,), dtype=np.int64)  # host mirror of
        # cache_len — the loop must not pay a device round-trip to know room
        # windows dispatched but not yet host-processed (_Window records):
        # admission-interleaved decode windows AND the steady-state
        # in-flight window both ride here; room accounting must include
        # their steps (_inflight_steps)
        self._deferred_windows: list[_Window] = []
        self._inflight_steps = 0
        # ... and, a lane, the steps it was GIVEN in them (a lane the
        # program parks inside a window has fewer than the window's): what
        # a lane's number for the next window leaves out
        # (``WindowScheduler.lane_steps``)
        self._lane_inflight = np.zeros((b,), dtype=np.int32)
        # ---- speculative decoding (ISSUE 5) ----
        # verify-graph length buckets (each is one compiled graph). A
        # single full-size bucket: on the paged path the verify cost is
        # gather-dominated, so a half-size bucket costs the same and can
        # never pay — adaptivity lives in the effective-acceptance gate
        # (_spec_gate), not in shrinking the graph
        self._spec_lens: tuple = (
            (engine_cfg.spec_len,) if engine_cfg.spec_len > 0 else ())
        self._spec_slots: list = [None] * b   # per-slot SlotSpecState
        self._spec_disabled_windows = 0
        self._stats = {"active_streams": 0, "queued": 0, "tokens_generated": 0,
                       "decode_steps": 0, "admit_dispatches": 0,
                       # lane-steps of dispatched decode windows that a lane
                       # live at dispatch sat out, parked by the program past
                       # its budget or cache room (ISSUE 65)
                       "decode_lane_steps_parked": 0,
                       # prefill chunks admitted, and those of them that
                       # went through the wide group forward
                       "admit_chunks": 0, "admit_chunks_grouped": 0,
                       "admit_interleaved_windows": 0,
                       # what those chunks held (ISSUE 57): the suffixes'
                       # real tokens, and the tokens their programs ran
                       # over (whole chunks)
                       "admit_tokens": 0, "admit_tokens_padded": 0,
                       # the tokens the prefix cache's key passed through
                       # its hash (ISSUE 58): one walk a prompt, so at most
                       # the prompts' own tokens. The cache counts them;
                       # ``stats()`` reads its count
                       "prefix_tokens_hashed": 0,
                       # a token's gap, told from inside (ISSUE 57): the
                       # tokens the windows delivered; summed over the
                       # lanes they delivered to, the seconds since each
                       # lane's delivery before, the part of them the
                       # admission clock moved by, and the windows' steps;
                       # the same period and steps over the lanes of the
                       # windows no admission touched; admissions begun
                       "gap_tokens": 0, "gap_lane_period_s": 0.0,
                       "gap_lane_admit_s": 0.0, "gap_lane_steps": 0,
                       "gap_clean_lane_period_s": 0.0,
                       "gap_clean_lane_steps": 0, "gap_admissions": 0,
                       # cached rows below a prefix hit that an admission
                       # prefilled again: the hit rounded down to a chunk
                       # (``_admit_lookup``'s fall-back)
                       "prefix_rows_recomputed": 0,
                       "spec_windows": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "deadline_expired": 0,
                       # kvwire (ISSUE 16): block-ship accounting — flat
                       # so the runner heartbeat forwards them unchanged
                       "kvwire_exports": 0, "kvwire_export_misses": 0,
                       "kvwire_blocks_exported": 0,
                       "kvwire_bytes_exported": 0,
                       "kvwire_blocks_imported": 0,
                       "kvwire_bytes_imported": 0,
                       "kvwire_import_hits": 0,
                       "kvwire_import_fallbacks": 0,
                       # kv tiering (ISSUE 20): paging + recompute
                       # accounting, flat for the heartbeat like kvwire
                       "kvtier_downpages": 0, "kvtier_uppages": 0,
                       "kvtier_uppage_failures": 0,
                       "kvtier_peer_spills": 0}
        # a looped decoder (ISSUE 34): tokens its decode windows produced,
        # the passes the device ran for them, and how often the head read
        # each pass. A plain decoder has none of the three: its programs
        # return no exit pass, so nothing here could move
        self._loop_exit_hist = [0] * cfg.loop_steps
        if cfg.looped:
            self._stats.update(loop_tokens=0, loop_passes=0)
        # a cache of window summaries (ISSUE 46): windows closed by prefill
        # chunks and inside decode programs, and, summed over the lanes of
        # every decode step, the tokens resident, the cache entries that
        # hold them (what the step's attention reads) and how many of those
        # are summaries. Plain attention has none of the five: an entry is
        # a token there
        # a layer pattern (ISSUE 48): the state its KDA layers keep by lane
        # (bytes; the lanes in use are the active ones), and the expert
        # layers' routing, counted here from the chosen experts a decode
        # window returns beside its tokens, over the LIVE lanes of every
        # decode step: picks that fell on held experts over token-layers
        # routed (2.0 where 2 of 8 picks are held and routing is uniform),
        # held experts touched over (step, layer)s, and how many picks each
        # held expert took. A plain expert decoder (ISSUE 50: it holds every
        # expert) counts the same from the picks ITS decode windows return,
        # which they do where a step reads only the experts its live lanes
        # picked (``moe.takes_held_form``; where the one-hot form serves —
        # int8 stacks, a mesh, a test's tiny experts — no window says and
        # the counters stay 0). A dense decoder has none of them
        # (no KDA layer, no state a lane: no names, and no lane program)
        self._lane_state_names = tuple(kvstate.lane_shapes(cfg, b))
        # state the pool keeps a BLOCK (short convolutions' tails): the pages
        # whose tails the splices wrote, and the admissions that started
        # behind a prefix hit with the tail of its last page restored
        if kvstate.block_tail_shapes(cfg, 1):
            self._stats.update(conv_tail_blocks_written=0,
                               conv_tail_restores=0)
        if not cfg.uniform:
            self._state_bytes = kvstate.lane_bytes(cfg, b)
        if cfg.n_experts:
            self._stats.update(moe_local_picks=0, moe_token_layers=0,
                               moe_held_touched=0, moe_step_layers=0)
            self._held_pick_hist = np.zeros((cfg.n_experts,), np.int64)
        # the prompt rows admitted and those of them a prefix hit reused:
        # counted where a hit is what the configuration is deployed for
        # (latent rows; rows beside short convolutions' tails)
        self._counts_prefix_rows = bool(cfg.mla_latent
                                        or cfg.lane_state == ("conv",))
        if self._counts_prefix_rows:
            self._stats.update(prefix_rows_reused=0, prompt_rows_admitted=0)
        if cfg.mla_latent:
            # latent attention (ISSUE 52), from the host's mirror of the
            # lengths: the cache rows the live lanes attend at every decode
            # step dispatched and those steps; the cache rows every chunk
            # or group of an admission attended (its own included) and the
            # (query, row) pairs its causal mask let through; the prompt
            # rows admitted and those of them a prefix hit reused
            self._stats.update(latent_rows_attended=0, latent_decode_steps=0,
                               prefill_rows_attended=0,
                               prefill_pairs_attended=0)
        if cfg.attn_window:
            self._stats.update(windows_closed_prefill=0,
                               windows_closed_decode=0,
                               decode_resident_tokens=0,
                               decode_resident_entries=0,
                               decode_summary_entries=0,
                               # the most pages the pool ever held in use
                               # (less the trash block), and the most by
                               # which they ever exceeded the reservations,
                               # which are in entries: 0, they never did
                               # (no prefix cache holds pages here)
                               kv_pages_used_peak=0,
                               kv_pages_over_reservation=0)
        # ---- observability (ISSUE 8) ----
        # flight recorder: bounded per-window ring (None = disabled)
        self.flight = flight_maybe(engine_cfg.flight_cap)
        # bring-up decomposition (ISSUE 13): load/compile_ahead/bind
        # seconds set by presets.load_engine, warmup_s by the runner —
        # stats() forwards them flat so the heartbeat can ship them into
        # the per-replica coldstart record
        self.bringup: dict = {}
        # execute-while-scaling readiness (ISSUE 17): weight groups bound
        # so far vs expected — set via note_group_bound() as the restore
        # streams, forwarded flat (scaleout_*) on the pressure heartbeat
        # so the router can admit per-group before the restore completes.
        # Empty = not a partial bring-up: ready_frac reports 1.0.
        self._scaleout_groups: dict = {"total": 0, "bound": []}
        # per-ENGINE latency registry (TTFT/TBT/queue-wait/prefill/decode
        # windows): its summaries ride stats() → the runner's pressure
        # heartbeat → /api/v1/metrics "engines". A process-global registry
        # would mix engines when two live in one process (bench A/B).
        self.metrics = Metrics()
        self._pick_reason = ""
        self._flight_kv_allocs = 0   # marker for per-record deltas
        # (lifetime allocation counter lives on the KvPool manager)
        self._flight_evictions = 0
        # on-demand jax.profiler hook (/rpc/llm/profile): a worker thread
        # traces a stated number of seconds (arm_profile)
        self._profile = {"active": False, "path": "", "seconds": 0.0,
                         "error": ""}
        self._profile_thread: Optional[threading.Thread] = None
        self._profile_cut = threading.Event()
        # host phases of the serve loop's thread (ISSUE 24): self seconds
        # and counts by phase name — stats() "host_phase_s"/"host_phase_n"
        self.host_phases = PhaseTotals()
        # ---- fleet timeline physics (ISSUE 12) ----
        # tokens/sec window: (monotonic, tokens_generated) pairs appended
        # on the stats() READ path (heartbeat cadence), zero serve-loop
        # cost; rate = delta over the retained window
        self._tps_window: list = []
        # per-chip decode physics constants: bytes streamed / matmul
        # FLOPs per generated token, so the CONTROL plane can price
        # MFU/MBU from heartbeated tokens/sec without importing model
        # internals. Decode is weight-streaming-bound: every step reads
        # the whole resident weight shard (KV bytes excluded — second-
        # order for the fleet-utilization signal this feeds).
        n_chips = max(int(self.policy.describe().get("n_chips", 1)), 1)

        def tree_size(tree) -> tuple:
            nbytes = count = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                size = getattr(leaf, "size", 0)
                nbytes += size * getattr(getattr(leaf, "dtype", None),
                                         "itemsize", 0)
                count += size
            return nbytes, count

        wb, nparams = tree_size(params)
        # a looped decoder streams its layers once a PASS, and a token
        # passes through them as often; what is resident (``wb``) stays
        # what the HBM prediction below prices
        lb, lparams = tree_size(params["layers"]) \
            if cfg.loop_steps > 1 else (0, 0)
        extra = cfg.loop_steps - 1
        self._phys_bytes_per_token_per_chip = (wb + extra * lb) / n_chips
        self._phys_flops_per_token_per_chip = \
            2.0 * (nparams + extra * lparams) / n_chips
        # the devices this engine is PLACED on, as jax reports them — the
        # runner's /health and heartbeat carry these, so a replica that
        # came up on the wrong backend is visible from outside the process
        self._devices = self.policy.devices()
        # which attention path each phase takes (pallas kernel, or the XLA
        # oracle with the reason the kernel declined these shapes)
        self._attention = self._attention_paths()
        self._pattern = self._pattern_report()
        # an expert layer that is told what it holds (a pattern's, by rule
        # or list) says in every program which experts each token chose,
        # and a finished request leaves them in ``routed_experts``
        self._keeps_routing = bool(cfg.moe_routed)
        # ---- replica health plane (ISSUE 14) ----
        # liveness watermark: monotonic progress counters + dispatch/
        # progress stamps the runner-side watchdog classifies from. All
        # stamped on host paths the loop already runs — zero new syncs.
        self._windows_processed = 0
        self._last_dispatch_mono = 0.0
        # the admission clock (ISSUE 57): seconds the serve loop has spent
        # in closed admission episodes, the start of the open one (0: none),
        # and the marks of the last window that delivered tokens
        self._admit_clock_s = 0.0
        self._admit_open_mono = 0.0
        self._gap_prev: tuple = ()
        self._last_progress_mono = time.monotonic()
        # HBM watermarks: live per-chip residency sampled on the stats()
        # READ path (heartbeat cadence) vs the planned residency computed
        # from the exact trees this engine holds — weights shard over
        # tp×fsdp, KV payload over the tp head shard (feasibility.py's
        # arithmetic, priced against the real leaves)
        self._hbm_peak_gb = 0.0
        topo = self.policy.describe()
        kvb = sum(getattr(leaf, "size", 0)
                  * getattr(getattr(leaf, "dtype", None), "itemsize", 0)
                  for leaf in jax.tree_util.tree_leaves(self.kv_cache))
        if self.paged:
            kvb += sum(
                getattr(leaf, "size", 0)
                * getattr(getattr(leaf, "dtype", None), "itemsize", 0)
                for leaf in jax.tree_util.tree_leaves(self._scratch))
        self.hbm_predicted_gb_per_chip = round(
            (wb / max(topo["tp"] * topo["fsdp"], 1)
             + kvb / max(topo["tp"], 1)) / 1e9, 3)
        # chip capacity is hardware-constant: sweep memory_stats() for it
        # ONCE here, not on every stats() read (the live-usage sweep is
        # the only per-beat memory_stats cost)
        self._hbm_limit_gb = self.policy.hbm_limit_gb_per_chip()
        # black box (ISSUE 14): the serve-loop failure handler snapshots
        # the forensic record HERE before fan-out clears the evidence;
        # the runner ships it to the gateway on the next heartbeat
        self.last_postmortem: Optional[dict] = None

    def _attention_paths(self) -> dict:
        """``{"decode", "prefill"}`` → ``"pallas"`` or ``"xla: <why>"`` for
        this engine's shapes, from the same predicates the dispatchers in
        ``ops.attention`` decide with when a program is traced (a paged
        engine's prefill: the chunk kernel at its chunk's and its admission
        group's widths; its decode names the paged kernel's body too, heads
        an update and pages a wave). A TPU replica whose attention declined
        a kernel still serves correctly through the oracle, so say why once
        here, where an operator reads the bring-up log."""
        if self.cfg.latent_rows:
            # a layer pattern: latent attention (``ops.latent_attention``)
            # and the KDA recurrence (``ops.delta_rule``), each a kernel on
            # the chip at whole tiles and ``jax.numpy`` elsewhere
            from ..ops.delta_rule import step_kernel_declined
            from ..ops.latent_attention import kernel_declined
            cfg = self.cfg

            def ran(why: str) -> str:
                return f"xla: {why}" if why else "pallas"
            from ..ops.latent_attention import (blocked_prefill_declined,
                                                prefill_kernel_declined)
            decode = "latent attention, absorbed: " + ran(
                kernel_declined(cfg.n_heads, cfg.mla_latent, cfg.mla_rope,
                                self.ecfg.kv_block_size, cfg.dtype))
            if blocked_prefill_declined(self.graphs.scratch_len):
                prefill = "xla: latent attention, expanded"
            else:
                prefill = "latent attention, blocked over keys: " + ran(
                    prefill_kernel_declined(
                        self.graphs.chunk, cfg.mla_nope, cfg.mla_rope,
                        cfg.mla_v, cfg.mla_latent, cfg.dtype))
            if not cfg.layers_of("kda"):
                return {"decode": decode, "prefill": prefill}
            return {"decode": decode + "; kda step: " + ran(
                        step_kernel_declined(cfg.n_heads, cfg.head_dim)),
                    "prefill": prefill + "; kda: chunkwise scan"}
        if self.cfg.lane_state == ("conv",):
            # a list around short convolutions: the plain attention's
            # kernels, and the mixers in ``jax.numpy`` (three multiplies and
            # two adds a channel between two matrix products)
            plain = self._plain_attention_paths()
            return {phase: f"{path}; short convolution: xla"
                    for phase, path in plain.items()}
        if self.cfg.lane_state:
            # a listed pattern: the plain attention's kernels below, and
            # the state-space recurrence (``ops.ssd``) beside them
            from ..models.ssm import scan_form, step_form
            plain = self._plain_attention_paths()
            widths = {self.graphs.chunk,
                      self.graphs.chunk * self.graphs.group_chunks}
            return {"decode": plain["decode"] + "; ssm step: "
                    + step_form(self.cfg),
                    "prefill": plain["prefill"] + "; ssm scan: " + ", ".join(
                        sorted({scan_form(w) for w in widths}))}
        return self._plain_attention_paths()

    def _pattern_report(self) -> dict:
        """What ``/health`` says of a layer pattern beside its attention
        paths: ``layers_by_kind`` (a half a listed layer lacks is not a
        kind), and for an expert layer that is told what it holds
        ``ffn_decode`` / ``ffn_prefill`` — the form it takes at a decode
        step's rows and at a prefill dispatch's, as ``attention_decode``
        says of the attention — and ``moe_latent`` (0 = none). Empty for a
        uniform decoder."""
        cfg, out = self.cfg, {}
        if not cfg.uniform:
            kinds = [k for layer in cfg.layers for k in layer if k != "none"]
            out["layers_by_kind"] = {k: kinds.count(k)
                                     for k in dict.fromkeys(kinds)}
        if cfg.moe_routed:
            from ..models.moe import share_forms
            from ..models.transformer import moe_cfg
            forms = share_forms(
                moe_cfg(cfg), self.ecfg.max_batch,
                (self.graphs.chunk,
                 self.graphs.chunk * self.graphs.group_chunks)
                if self.paged else self.ecfg.prefill_buckets)
            out.update(ffn_decode=forms["decode"],
                       ffn_prefill=forms["prefill"],
                       moe_latent=cfg.moe_latent_dim)
        return out

    def _plain_attention_paths(self) -> dict:
        """:meth:`_attention_paths` of the plain attention's kernels."""
        from ..ops import attention as ops
        hd = self.cfg.head_dim
        s_max = self.ecfg.max_seq_len
        ran = "pallas"
        if self.paged:
            decode = ops.paged_kernel_declined(self.ecfg.kv_block_size, hd)
            ran = ops.paged_kernel_form(self.kv_cache["k"], self.cfg.n_heads,
                                        self._mb, self.policy.mesh)
            # a chunk and an admission group are the two widths admitted
            chunk = self.graphs.chunk
            s_max = self.graphs.scratch_len
            reasons = {ops.chunk_kernel_declined(t, s_max, hd)
                       for t in (chunk, chunk * self.graphs.group_chunks)}
        else:
            decode = ops.ragged_kernel_declined(s_max, hd)
            reasons = {ops.flash_kernel_declined(bk, bk, hd)
                       for bk in self._buckets}
        prefill = "; ".join(sorted(reasons - {""}))
        if decode and self._devices[0].platform == "tpu":
            logging.getLogger("tpu9.serving").warning(
                "decode attention runs the XLA oracle, not the pallas "
                "kernel: %s", decode)
        return {"decode": f"xla: {decode}" if decode else ran,
                "prefill": f"xla: {prefill}" if prefill else "pallas"}

    # -- compiled steps (serving.graphs) + scheduling (serving.schedule) ----
    # Thin delegates: the implementations moved out with the ISSUE 9
    # engine split; these names are the engine's stable internal surface
    # (bench and the spec/paged tests exercise them directly).

    def _decode_k(self, k: int):
        return self.graphs.decode_k(k)

    def _verify_fn(self, s: int):
        return self.graphs.verify_fn(s)

    def _prefill_fn(self, bucket: int):
        return self.graphs.prefill_fn(bucket)

    def _dense_splice_fn(self, bucket: int):
        return self.graphs.dense_splice_fn(bucket)

    def _chunk_fn(self):
        return self.graphs.chunk_fn()

    def _gather_fn(self):
        return self.graphs.gather_fn()

    def _splice_fn(self):
        return self.graphs.splice_fn()

    def _chunk_group_fn(self, g: int):
        return self.graphs.chunk_group_fn(g)

    def _admission_can_proceed(self) -> bool:
        return self.scheduler.admission_can_proceed()

    def _pick_steps(self, left=None) -> int:
        return self.scheduler.pick_steps(left)

    def _spec_room_len(self) -> int:
        return self.scheduler.spec_room_len()

    def _spec_gate(self, s: int) -> int:
        return self.scheduler.spec_gate(s)

    def _bucket_for(self, n: int) -> int:
        # buckets are CLAMPED to max_seq_len: a configured bucket wider
        # than the cache (e.g. default (128,512,2048) with max_seq 1024)
        # would make the splice a trace-time error that kills the loop
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    # -- paged-KV machinery (graphs live in serving.graphs; block/table
    # bookkeeping in serving.kvpool) ----------------------------------------

    def _pool_dict(self) -> dict:
        """The kv pool's array view (payload + scales and what it keeps a
        block, no table) — the pytree the splice/gather/fused-group graphs
        take and return."""
        return {k: self.kv_cache[k] for k in self.pool.program_names()}

    def _set_pool(self, pool: dict) -> None:
        self.kv_cache.update(pool)

    def _worst_case_tokens(self, req: _Request) -> int:
        # prompt + full generation budget + slack, clamped to the cache:
        # positions never exceed max_seq_len, so a near-max prompt must not
        # over-reserve itself into rejection. A decode window writes no row
        # past ``prompt + max_new_tokens`` (the program parks a lane at its
        # budget: ISSUE 65), so for classic decode the slack is only spare.
        # With speculation on, up to TWO verify windows can be in flight
        # past the budget check (the steady-state overlap window plus the
        # one being dispatched), so the slack covers 2·(1+spec_len).
        slack = max(self.ecfg.decode_steps) + 1
        if self._spec_lens:
            slack = max(slack, 2 * (self._spec_lens[-1] + 1) + 1)
        # (in cache entries, which is what the allocator's blocks hold)
        return self.cfg.kv_entries_peak(
            min(len(req.prompt) + req.max_new_tokens + slack,
                self.ecfg.max_seq_len))

    def _alloc_blocks(self, n: int) -> list[int]:
        got = self.pool.alloc_blocks(n)
        if self.cfg.attn_window:
            self._note_pages()
        return got

    def _note_pages(self) -> None:
        """After an allocation of a model with ``attn_window``: pages in
        use, and against pages reserved."""
        st = self._stats
        used = self.allocator.used_count - 1
        st["kv_pages_used_peak"] = max(st["kv_pages_used_peak"], used)
        st["kv_pages_over_reservation"] = max(
            st["kv_pages_over_reservation"], used - self.allocator.reserved)

    def _note_decode_entries(self, k: int, given) -> None:
        """The latent and summary-cache counters of one decode window of
        ``k`` steps about to be dispatched, from the host's mirror of the
        lengths: step ``i`` of a lane that holds ``n`` tokens writes position
        ``n + i`` and attends ``kv_entries(n + i + 1)`` entries — in the
        ``given[lane]`` steps it runs; the steps a lane sits out parked read
        nothing, and are counted as nothing."""
        cfg = self.cfg
        if not (cfg.mla_latent or cfg.attn_window):
            return
        on = self.active
        lanes = self._host_len[on] + self._lane_inflight[on]
        pos = lanes[:, None] + np.arange(k)[None, :]      # written positions
        run = np.arange(k)[None, :] < given[on][:, None]  # not parked
        if cfg.mla_latent:
            # step ``i`` of a lane that holds ``n`` tokens attends ``n + i +
            # 1`` latent rows in every MLA layer
            self._stats["latent_rows_attended"] += int((pos + 1)[run].sum())
            self._stats["latent_decode_steps"] += k
        if not cfg.attn_window:
            return
        pos = pos[run]
        windows = pos // cfg.attn_window                  # closed before it
        st = self._stats
        st["windows_closed_decode"] += int(
            ((pos > 0) & (pos % cfg.attn_window == 0)).sum())
        st["decode_resident_tokens"] += int((pos + 1).sum())
        st["decode_resident_entries"] += int(cfg.kv_entries(pos + 1).sum())
        st["decode_summary_entries"] += int(
            (windows * cfg.window_entries).sum())

    def _push_table(self, slot: int) -> None:
        self.kv_cache["table"] = self.pool.push_table(slot)

    def _ensure_slot_blocks(self, slot: int, n_tokens: int) -> bool:
        """Grow the slot's physical block list to cover ``n_tokens``
        positions. Returns True when the table changed."""
        if not self.pool.ensure_slot_blocks(slot, n_tokens):
            return False
        if self.cfg.attn_window:
            self._note_pages()
        self._push_table(slot)
        return True

    # -- public API ----------------------------------------------------------

    async def start(self) -> None:
        if self._loop_task is None:
            self._loop_task = asyncio.create_task(self._serve_loop())

    def bind_params(self, params: Params) -> None:
        """Swap the engine onto real weights. The compile-ahead path
        constructs the engine with an ABSTRACT param tree
        (``jax.ShapeDtypeStruct`` leaves — see :func:`abstract_params`),
        precompiles while the weights stream, then binds the streamed /
        pooled arrays here. The engine must not serve before this.
        Placement goes through the sharding policy: a mesh engine shards
        the tree per ``decoder_param_specs`` here (already-sharded arrays
        device_put to their own sharding, a no-op)."""
        self.params = self.policy.place_params(params)

    def note_group_bound(self, group: str, total: int) -> None:
        """Execute-while-scaling bookkeeping (ISSUE 17): one weight group
        of a streaming restore has been bound. The engine itself binds a
        complete tree via :meth:`bind_params`; THIS records which groups
        have arrived so the pressure heartbeat reports per-group
        readiness and the router can admit matching requests before the
        final group lands."""
        sg = self._scaleout_groups
        sg["total"] = max(int(total), sg["total"])
        if group and group not in sg["bound"]:
            sg["bound"].append(group)

    def precompile(self) -> dict:
        """AOT-compile every steady-state serving graph from SHAPES alone.

        XLA needs param shapes/dtypes, not values — so serving bring-up can
        run this concurrently with weight streaming (``self.params`` may be
        a ``jax.ShapeDtypeStruct`` tree from :func:`abstract_params`)
        instead of serializing a multi-second compile behind the weight
        load. Each ``.lower(...).compile()`` executable replaces the jitted
        function under the same cache key the serve loop resolves, so after
        ``bind_params`` the warmup/serve path dispatches straight into the
        compiled graph; with ``JAX_COMPILATION_CACHE_DIR`` set (every tpu9
        container) the executables land in the persistent cache too.
        Scalar positions are lowered with concrete ints — the weak-typed
        aval the serve loop's python-int arguments produce. The AOT logic
        itself lives with the graphs (``GraphFactory.precompile``); on a
        mesh policy the lowered specs carry the shardings, so the
        executables are the exact SPMD programs the serve loop runs."""
        return self.graphs.precompile(
            self.params, self.kv_cache,
            self._pool_dict() if self.paged else {},
            self._scratch if self.paged else {},
            self._mb if self.paged else 0,
            self._buckets, self._spec_lens, self._rng)

    def warmup(self) -> dict:
        """Precompile every prefill bucket and decode-window graph.

        Production engines pay XLA compiles at boot, not on the first user
        request: an 8B decode graph takes ~10 s to compile, and a window
        size that first occurs mid-traffic (e.g. K=1 when retirements
        stagger) would stall the whole decode batch behind a compile. Runs
        each graph once with all-inactive lanes (state is threaded back, so
        this is a no-op for correctness) and fences with a device→host copy.
        """
        import time as _time
        timings: dict[str, float] = {}
        if self.paged:
            # paged prefill path: chunk + splice + gather graphs
            t0 = _time.perf_counter()
            toks = jnp.zeros((1, self._chunk), jnp.int32)
            last, self._scratch, *_picks = self._chunk_fn()(
                self.params, toks, 0, self._scratch, 0)
            np.asarray(jax.device_get(last[:4]))
            timings[f"chunk_{self._chunk}_s"] = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            phys = jnp.full(self.graphs.splice_shape(1), self._trash_block,
                            jnp.int32)
            self._set_pool(self._splice_fn()(
                self._pool_dict(), self._scratch["k"], self._scratch["v"],
                0, phys, *self.graphs.scratch_tails(self._scratch)))
            # as in admission: the gathered copy REPLACES the scratch, and
            # the old one is let go first so that the two are never live
            # together (1.6 GB each where the KV state is 192 planes deep)
            # (a layer pattern's scratch also carries one lane of KDA
            # state, which no gather replaces)
            kept = {n: a for n, a in self._scratch.items()
                    if n not in ("k", "v")}
            self._scratch = None
            dense = self._gather_fn()(self._pool_dict(),
                                      self.kv_cache["table"][0])
            np.asarray(jax.device_get(dense["k"].ravel()[:4]))
            self._scratch = {**kept, "k": dense["k"], "v": dense["v"]}
            del dense
            timings["splice_gather_s"] = _time.perf_counter() - t0
            if self.graphs.restores_tails:
                # a prefix hit's read of a page's tails (the trash block's)
                self._scratch.update(self.graphs.restore_tails(
                    self.kv_cache, self._trash_block))
            if self._lane_state_names:
                t0 = _time.perf_counter()
                self._splice_lane_state(0)     # zeros over an idle lane
                timings["lane_splice_s"] = _time.perf_counter() - t0
            g = self.graphs.group_chunks
            if g > 1:
                # fused admission graph for the steady-state group size.
                # Partial tails never need a width of their own:
                # _admit_paged drops to the warmed single-chunk graphs
                # for them, so this IS the last reachable signature
                # (graphcheck GRA005 / the recompile sentinel both
                # assert the set is closed here)
                t0 = _time.perf_counter()
                pool, self._scratch, last, *_picks = self._chunk_group_fn(g)(
                    self.params, self._pool_dict(), self._scratch,
                    jnp.zeros((g, self._chunk), jnp.int32), 0,
                    self._chunk - 1,
                    jnp.full(self.graphs.splice_shape(g), self._trash_block,
                             jnp.int32))
                self._set_pool(pool)
                np.asarray(jax.device_get(last[:4]))
                timings[f"chunk_group_{g}_s"] = _time.perf_counter() - t0
        else:
            for bucket in self._buckets:
                t0 = _time.perf_counter()
                tokens = jnp.zeros((1, bucket), jnp.int32)
                last, cache = self._prefill_fn(bucket)(self.params,
                                                       tokens, 1)
                np.asarray(jax.device_get(last[:4]))
                timings[f"prefill_{bucket}_s"] = _time.perf_counter() - t0
                # the dense splice too (ISSUE 11): warmup previously left
                # it to compile on the FIRST admission — a post-seal
                # cache miss the recompile sentinel now counts as a
                # mid-serve stall. State threads back (slot 0's lanes get
                # the zero-prompt prefix; cache_len stays 0, so nothing
                # ever attends it).
                t0 = _time.perf_counter()
                self.kv_cache["k"], self.kv_cache["v"] = \
                    self._dense_splice_fn(bucket)(
                        self.kv_cache["k"], self.kv_cache["v"],
                        cache["k"], cache["v"], 0)
                timings[f"dsplice_{bucket}_s"] = _time.perf_counter() - t0
        inactive = jnp.zeros((self.ecfg.max_batch,), bool)
        no_steps = jnp.zeros((self.ecfg.max_batch,), jnp.int32)
        for k in self.ecfg.decode_steps:
            t0 = _time.perf_counter()
            (self.last_token, self.kv_cache, self.cache_len, self._rng,
             toks, *_exits) = self._decode_k(k)(
                self.params, self.kv_cache, self.last_token,
                self.cache_len, no_steps, self._rng)
            np.asarray(jax.device_get(toks[-1, :4]))
            timings[f"decode_k{k}_s"] = _time.perf_counter() - t0
        for s in self._spec_lens:
            # speculative verify graphs: a spec window that first occurs
            # mid-traffic must not stall the batch behind an XLA compile
            t0 = _time.perf_counter()
            drafts = jnp.zeros((self.ecfg.max_batch, s), jnp.int32)
            (self.last_token, self.kv_cache, self.cache_len, self._rng,
             out, _n) = self._verify_fn(s)(
                self.params, self.kv_cache, self.last_token, drafts,
                self.cache_len, inactive, self._rng)
            np.asarray(jax.device_get(out[:4, 0]))
            timings[f"verify_s{s}_s"] = _time.perf_counter() - t0
        # recompile sentinel (ISSUE 11): warmup traced every steady-state
        # graph; from here a cache miss is a mid-serve compile incident
        self.graphs.seal()
        return timings

    async def stop(self) -> None:
        thread = self._profile_thread
        if thread is not None and thread.is_alive():
            # a dangling device trace outlives the engine otherwise: cut
            # the armed seconds short and wait for the dump off the loop
            self._profile_cut.set()
            await asyncio.get_running_loop().run_in_executor(
                None, thread.join)
            await asyncio.sleep(0)      # the thread's "stopped" record
        if self._loop_task:
            # reap: absorbs the loop's CancelledError AND an Exception exit
            # (the loop ALREADY died; its failure was logged + fanned out)
            # but re-raises if stop() itself is cancelled (ASY003)
            await reap(self._loop_task, absorb_errors=True)
            self._loop_task = None
        # a clean shutdown must not strand callers: anything still
        # admitted/waiting/queued gets a terminal answer (the loop's
        # failure handler only covers Exception, not CancelledError)
        self._fail_all_requests("engine stopped")

    def cancel_request(self, req: "_Request") -> None:
        """Abandon a request (client disconnected mid-stream): the serve
        loop retires its slot at the next host sync instead of decoding
        the full budget into a queue nobody reads."""
        req.cancelled = True
        if req.done.is_set():
            return
        if req in self._wait_room:
            self._wait_room.remove(req)
            if req.queue is not None:
                req.queue.put_nowait(None)
            req.done.set()

    def active_stream_requests(self) -> list:
        """Live streaming requests (queue-backed, not cancelled) — what a
        graceful drain walks to migrate in-flight generations (ISSUE 16).
        The runner pushes dict events (``kv_key`` announcements) straight
        into these queues; the SSE relay forwards them verbatim."""
        return [req for slot, req in enumerate(self.slot_req)
                if req is not None and self.active[slot]
                and req.queue is not None and not req.cancelled]

    async def generate(self, prompt: list[int], max_new_tokens: int = 32,
                       request_id: str = "", stream: bool = False,
                       trace: Optional[tuple] = None,
                       budget_s: Optional[float] = None):
        """``trace`` is an optional remote span context ``(trace_id,
        parent_span_id)`` — set by the llm runner from the gateway's
        X-Tpu9-Trace header — under which the engine records its
        request/prefill/decode-window spans. None (the default) records
        no spans; latency metrics and the flight recorder are always on.

        ``budget_s`` (ISSUE 15) is the request's remaining deadline
        budget in seconds: a request still queued past it is never
        prefilled, and a slot still decoding past it is retired at the
        next window boundary (its KV blocks return to the pool
        immediately). None disables the deadline."""
        if self._dead_reason is not None:
            raise RuntimeError(
                f"engine is dead: {self._dead_reason} (restart the "
                "container — requests would hang forever)")
        if budget_s is not None and budget_s <= 0:
            raise TimeoutError(f"{DEADLINE_ERROR}: budget exhausted "
                               "before admission")
        # chunked prefill (paged mode) has no bucket cap — only the cache
        limit = self.ecfg.max_seq_len - 1 if self.paged else \
            min(self._buckets[-1], self.ecfg.max_seq_len - 1)
        if len(prompt) > limit:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds engine limit {limit}")
        if not prompt:
            raise ValueError("empty prompt")
        req = _Request(request_id=request_id or f"r{time.monotonic_ns()}",
                       prompt=list(prompt), max_new_tokens=max_new_tokens,
                       queue=asyncio.Queue() if stream else None,
                       trace=trace if trace and trace[0] else None,
                       t_enqueue_mono=time.monotonic(),
                       t_enqueue_wall=time.time(),
                       deadline_mono=(time.monotonic() + budget_s
                                      if budget_s else 0.0))
        await self._queue.put(req)
        self._stats["queued"] = self._queue.qsize()
        if stream:
            return req  # caller iterates req.queue
        await req.done.wait()
        if req.error:
            if req.error.startswith(DEADLINE_ERROR):
                raise TimeoutError(req.error)
            if req.error.startswith("engine"):
                # infrastructure failure (serve loop died / engine
                # stopped), not a request-shape problem: the runner maps
                # this to 500 and the gateway's failover retries it
                raise RuntimeError(req.error)
            raise ValueError(req.error)
        return req.generated

    # -- kvwire export / adopt (ISSUE 16) ------------------------------------
    # Synchronous by design: these run on the event loop between awaits,
    # so slot/allocator/prefix-cache state cannot shift underneath them
    # (the same atomicity the serve loop itself relies on). The device
    # reads inside block the loop for the gather duration — acceptable
    # for rare control-plane operations (handoff, drain, failover), and
    # XLA orders them after any in-flight window on the same arrays.

    def export_prefix_kv(self, tokens: list[int]) -> Optional[bytes]:
        """Serialize the longest prefix-cached block run covering
        ``tokens`` into a kvwire payload (None = nothing cached). The
        entry stays PINNED across the gather so a concurrent admission's
        eviction cannot recycle a block mid-device_get."""
        if not self.paged or self.ecfg.prefix_cache_blocks <= 0 \
                or self.cfg.lane_state:
            # (state a lane: the format ships rows and no tail a block)
            return None
        entry = self.prefix_cache.acquire_for_export(list(tokens))
        if entry is None:
            self._stats["kvwire_export_misses"] += 1
            return None
        t0 = time.perf_counter()
        try:
            payload = self.pool.export_blocks(
                self.kv_cache, entry.blocks, entry.key, entry.n_tokens)
        finally:
            self.prefix_cache.release_pin(entry)
        self.metrics.observe("tpu9_kvwire_export_s",
                             time.perf_counter() - t0)
        self._stats["kvwire_exports"] += 1
        self._stats["kvwire_blocks_exported"] += len(entry.blocks)
        self._stats["kvwire_bytes_exported"] += len(payload)
        return payload

    def export_request_kv(self, request_id: str) -> Optional[bytes]:
        """Serialize an IN-FLIGHT request's full-block KV prefix (prompt
        + generated so far) — the drain-migration export. The slot's own
        block refs keep the blocks alive for the synchronous gather; the
        in-flight decode window only ever writes positions past the
        delivered sequence, which land in blocks beyond the shipped run.
        None = request not active or under one full block — or a pool of
        window summaries, whose blocks are addressed by cache entry and not
        by token position (the kvwire format's ``n_tokens``): the caller
        re-prefills, as for any miss."""
        if not self.paged or self.cfg.attn_window or not self.cfg.uniform:
            # (a layer pattern: the format ships rows and no state a lane)
            return None
        for slot in range(self.ecfg.max_batch):
            req = self.slot_req[slot]
            if req is None or not self.active[slot] \
                    or req.request_id != request_id:
                continue
            seq = req.prompt + req.generated
            bs = self.ecfg.kv_block_size
            nb = min(len(seq) // bs, len(self._slot_blocks[slot]))
            if nb <= 0:
                return None
            t0 = time.perf_counter()
            payload = self.pool.export_blocks(
                self.kv_cache, self._slot_blocks[slot][:nb],
                self.prefix_cache.walk(seq[:nb * bs])[-1], nb * bs)
            self.metrics.observe("tpu9_kvwire_export_s",
                                 time.perf_counter() - t0)
            self._stats["kvwire_exports"] += 1
            self._stats["kvwire_blocks_exported"] += nb
            self._stats["kvwire_bytes_exported"] += len(payload)
            return payload
        return None

    def adopt_kv(self, payload: bytes) -> bool:
        """Splice a kvwire payload into fresh pool blocks and adopt the
        prefix into the cache, so the next ``generate`` over those tokens
        admits through the ordinary prefix-reuse path (chunked suffix
        prefill from the shipped watermark). False = could not adopt
        (pool pressure / prefix budget) — the caller falls back to plain
        re-prefill. Malformed payloads raise :class:`KvWireError` before
        any pool mutation."""
        if not self.paged or self.ecfg.prefix_cache_blocks <= 0 \
                or self.cfg.lane_state:
            # (state a lane: a payload's pages would come without the tails
            # a hit behind them starts from)
            self._stats["kvwire_import_fallbacks"] += 1
            return False
        t0 = time.perf_counter()
        try:
            kv, adopted, header = self.pool.import_blocks(
                self.kv_cache, payload)
        except RuntimeError:
            # pool exhausted mid-splice: not an error, just no room —
            # re-prefill serves the request from scratch
            self._stats["kvwire_import_fallbacks"] += 1
            return False
        self.kv_cache = kv
        if not adopted:
            self._stats["kvwire_import_fallbacks"] += 1
            return False
        self.metrics.observe("tpu9_kvwire_import_s",
                             time.perf_counter() - t0)
        self._stats["kvwire_import_hits"] += 1
        self._stats["kvwire_blocks_imported"] += int(
            header.get("n_blocks", 0))
        self._stats["kvwire_bytes_imported"] += len(payload)
        return True

    def note_kvwire_ship(self, seconds: float) -> None:
        """Transport-side ship latency (cache put/get round-trip), fed by
        the runner — the engine itself never touches the transport."""
        self.metrics.observe("tpu9_kvwire_ship_s", seconds)

    def note_kvwire_fallback(self) -> None:
        """A ship that never reached import (fetch failed / fault
        injected): counted so hit-vs-fallback covers the whole path."""
        self._stats["kvwire_import_fallbacks"] += 1

    def flight_records(self, limit: int = 256,
                       since_seq: int = 0) -> list[dict]:
        """Flight-recorder tail (newest last); [] when disabled. The
        runner's /flight RPC and bench read through here so neither needs
        to know whether the recorder is on."""
        if self.flight is None:
            return []
        return self.flight.snapshot(limit=limit, since_seq=since_seq)

    def blackbox(self, reason: str, exception: str = "") -> dict:
        """Raw forensic material for a post-mortem record (ISSUE 14):
        scalar stats, scheduler + KV-pool state, HBM breakdown, the
        flight-recorder tail and the engine's recent spans. Plain host
        reads only — safe to call from a failure handler or next to a
        wedged serve loop. The runner wraps this through
        ``tpu9.observability.health.build_postmortem`` (the size bound)
        before shipping; the engine itself never imports the health
        module, keeping the observability leaf reverse-edge-free."""
        stats = self.stats()
        scheduler = {
            "active_slots": [int(i) for i in range(self.ecfg.max_batch)
                             if self.active[i]],
            "slot_requests": {
                str(i): req.request_id
                for i, req in enumerate(self.slot_req) if req is not None},
            "slot_generated": {
                str(i): len(req.generated)
                for i, req in enumerate(self.slot_req) if req is not None},
            "queued": self._queue.qsize(),
            "wait_room": len(self._wait_room),
            "admitting": (self._admitting.request_id
                          if self._admitting else ""),
            "inflight_steps": self._inflight_steps,
            "deferred_windows": len(self._deferred_windows),
            "pick_reason": self._pick_reason,
        }
        kv_pool = {}
        if self.paged:
            kv_pool = {"n_blocks": self.allocator.n_blocks,
                       "block_size": self.allocator.block_s,
                       "used": self.allocator.used_count,
                       "free": self.allocator.free_count,
                       "reserved": self.allocator.reserved,
                       "lifetime_allocs": self.pool.kv_allocs,
                       "kv_quant": self.ecfg.kv_quant if self.kv_quant
                       else ""}
            if self.prefix_cache is not None:
                kv_pool["prefix_cache"] = self.prefix_cache.stats()
        hbm = {k: stats.get(k, 0.0)
               for k in ("hbm_used_gb_per_chip", "hbm_peak_gb_per_chip",
                         "hbm_predicted_gb_per_chip",
                         "hbm_limit_gb_per_chip")}
        return {
            "reason": reason,
            "exception": exception,
            "stats": {k: v for k, v in stats.items()
                      if isinstance(v, (int, float, str, bool))},
            "scheduler": scheduler,
            "kv_pool": kv_pool,
            "hbm": hbm,
            "flight": self.flight_records(limit=64),
            "spans": tracer.export(limit=128),
        }

    def stats(self) -> dict:
        out = dict(self._stats)
        if not self.paged or not self.pool.tiered:
            # untiered stats surface is byte-identical to pre-tiering:
            # no kvtier_ family for the heartbeat/directory to chew on
            for k in [k for k in out if k.startswith("kvtier_")]:
                del out[k]
        out["active_streams"] = int(self.active.sum())
        out["queued"] = self._queue.qsize()
        out["engine_dead"] = self._dead_reason is not None
        # host mirror, NOT device_get: a blocking read here would stall
        # the event loop (health checks, SSE) behind the in-flight decode
        # window
        out["token_pressure"] = float(
            self._host_len.sum()
            / (self.ecfg.max_batch * self.ecfg.max_seq_len))
        # recompile sentinel (ISSUE 11): executable-cache misses. A
        # non-zero post_warmup count after warmup/precompile means a
        # serve-loop dispatch stalled every stream behind an XLA compile
        # — the runtime face of graphcheck's closed-signature invariant
        # (the factory also logs each incident loudly).
        out["graph_compiles"] = self.graphs.compiles
        out["graph_compiles_post_warmup"] = self.graphs.post_seal_compiles
        # cumulative seconds serving stalled behind those compiles — the
        # goodput accountant's recompile_stall bucket (ISSUE 12)
        out["graph_compile_stall_s"] = round(
            self.graphs.post_seal_stall_s, 6)
        # ---- fleet timeline series (ISSUE 12) ----
        # tokens/sec over the retained read-path window: each stats()
        # call (heartbeat cadence) appends the cumulative counter and
        # rates the delta — no serve-loop instrumentation at all
        now_m = time.monotonic()
        self._tps_window.append((now_m, self._stats["tokens_generated"]))
        while (len(self._tps_window) > 2
               and now_m - self._tps_window[0][0] > 30.0):
            self._tps_window.pop(0)
        t0, c0 = self._tps_window[0]
        span = now_m - t0
        out["tokens_per_sec"] = round(
            (self._stats["tokens_generated"] - c0) / span, 3) \
            if span > 0.5 else 0.0
        # decode physics constants + device kind: the gateway prices
        # MFU/MBU timeline series from these (benchsuite.physics specs
        # stay control-plane-side; the engine ships raw arithmetic)
        out["decode_bytes_per_token_per_chip"] = \
            self._phys_bytes_per_token_per_chip
        out["decode_flops_per_token_per_chip"] = \
            self._phys_flops_per_token_per_chip
        # the depth of the KV state and what one token costs the pool,
        # whole model: a looped decoder keeps a plane a (pass, layer)
        out["kv_layers"] = self.cfg.kv_layers
        from ..models import kvstate
        out["kv_bytes_per_token"] = kvstate.block_bytes(self.cfg, 1,
                                                        self.kv_quant)
        if self.cfg.looped:
            out["loop_steps"] = self.cfg.loop_steps
            out["loop_exit_hist"] = list(self._loop_exit_hist)
        if not self.cfg.uniform:
            out["state_kinds"] = list(self.cfg.lane_state)
            out["state_bytes"] = self._state_bytes
            out["state_bytes_per_lane"] = \
                self._state_bytes // self.ecfg.max_batch
            out["state_lanes_in_use"] = int(self.active.sum())
        if self.cfg.n_experts:
            out["moe_experts_held"] = self.cfg.n_experts
            out["moe_held_pick_hist"] = [int(n) for n in
                                         self._held_pick_hist]
        out["device_platform"] = self._devices[0].platform
        out["device_kind"] = self._devices[0].device_kind
        out["device_count"] = len(self._devices)
        out["attention_decode"] = self._attention["decode"]
        out["attention_prefill"] = self._attention["prefill"]
        # a pattern's layers by kind; which form its expert layer takes at
        # a decode step's rows and at a prefill dispatch's, and the
        # experts' latent (``_pattern_report``)
        out.update(self._pattern)
        # tpu_custom_call count per AOT-compiled graph (precompile only)
        out["graph_kernels"] = dict(self.graphs.kernel_calls)
        # topology (ISSUE 9): flat scalars so the runner heartbeat can
        # forward them into the store hash behind /api/v1/metrics
        # "engines" unchanged — tp/fsdp/n_chips plus live per-chip HBM
        # (max across the submesh; 0.0 where the backend has no memory
        # stats, i.e. CPU). A 1x1 engine reports tp=1 so the fleet view
        # can tell "single chip" from "not reporting".
        topo = self.policy.describe()
        out["topo_tp"] = topo["tp"]
        out["topo_fsdp"] = topo["fsdp"]
        out["topo_n_chips"] = topo["n_chips"]
        mem = self.policy.memory_stats()     # one sweep per stats() read
        out["hbm_used_gb_by_chip"] = self.policy.hbm_gb_by_chip(stats=mem)
        out["hbm_used_gb_per_chip"] = max(out["hbm_used_gb_by_chip"],
                                          default=0.0)
        # ---- replica health plane (ISSUE 14) ----
        # liveness watermark: progress counters + dispatch/progress ages
        # the runner-side watchdog classifies ok/degraded/stalled from.
        # Ages are computed here (one clock) so the watchdog never has to
        # correlate monotonic clocks across the RPC boundary.
        out["windows_processed"] = self._windows_processed
        out["last_dispatch_age_s"] = (
            round(now_m - self._last_dispatch_mono, 3)
            if self._last_dispatch_mono else -1.0)
        out["last_progress_age_s"] = round(
            now_m - self._last_progress_mono, 3)
        # HBM watermarks: peak is the device's own high-water mark (max
        # across the submesh), never below a read-path sample; predicted is
        # the planner-arithmetic residency of the exact trees this engine
        # holds; limit is the chip's capacity (0.0 where the backend has no
        # memory stats, i.e. CPU)
        self._hbm_peak_gb = max(
            self._hbm_peak_gb, out["hbm_used_gb_per_chip"],
            *self.policy.hbm_gb_by_chip("peak_bytes_in_use", stats=mem))
        out["hbm_peak_gb_per_chip"] = self._hbm_peak_gb
        out["hbm_predicted_gb_per_chip"] = self.hbm_predicted_gb_per_chip
        out["hbm_limit_gb_per_chip"] = self._hbm_limit_gb
        # speculative-decoding acceptance (ISSUE 5): proposed/accepted are
        # cumulative; the rate is the fleet-comparable signal the runner
        # heartbeats and the router aggregates
        out["spec_enabled"] = bool(self._spec_lens)
        prop = self._stats["spec_proposed"]
        out["spec_acceptance_rate"] = (
            self._stats["spec_accepted"] / prop if prop else 0.0)
        # flight recorder + profiling hook + latency decomposition
        # (ISSUE 8). "latency" is flat p50/p95/count scalars per phase so
        # the runner heartbeat can forward them into the store hash that
        # backs /api/v1/metrics "engines" unchanged.
        if self.flight is not None:
            out["flight"] = self.flight.summary()
        out["profile"] = dict(self._profile)
        # host phases of the serve loop's thread (ISSUE 24): self seconds
        # and counts by phase, cumulative — where the loop's time goes,
        # for an operator without a profiler. Nested: kept off the flat
        # heartbeat, read whole off GET /health.
        out["host_phase_s"] = {k: round(v[1], 6)
                               for k, v in self.host_phases.items()}
        out["host_phase_n"] = {k: v[0]
                               for k, v in self.host_phases.items()}
        # which scope (jax.named_scope) each instruction of a precompiled
        # program belongs to: a trace names device operations by their
        # HLO instruction, and this is the way back to the model's parts
        if self.graphs.device_scopes:
            out["device_scopes"] = self.graphs.device_scopes
        # cold-start decomposition (ISSUE 13): flat coldstart_* scalars so
        # the runner heartbeat forwards them into the pressure hash that
        # backs /api/v1/metrics "engines" and /api/v1/coldstart unchanged
        for k, v in self.bringup.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"coldstart_{k}"] = v
        # execute-while-scaling readiness (ISSUE 17): flat scaleout_*
        # scalars, same heartbeat-forwarding contract as coldstart_*.
        # No partial bring-up in flight (total == 0) reports fully ready
        # so steady-state replicas are indistinguishable from before.
        sg = self._scaleout_groups
        out["scaleout_groups_total"] = sg["total"]
        out["scaleout_groups_ready"] = len(sg["bound"])
        out["scaleout_ready_frac"] = round(
            len(sg["bound"]) / sg["total"], 4) if sg["total"] else 1.0
        out["scaleout_ready_groups"] = ",".join(sg["bound"])
        lat = {}
        summaries = self.metrics.to_dict()["summaries"]
        for part in ("ttft", "queue_wait", "prefill", "first_hold",
                     "stream_lag", "ingest", "runner_first",
                     "decode_window", "e2e", "tpot", "gap_max",
                     "runner_gap"):
            snap = summaries.get(f"tpu9_engine_{part}_s")
            if snap:
                lat[f"{part}_p50_s"] = round(snap["p50"], 6)
                lat[f"{part}_p95_s"] = round(snap["p95"], 6)
                lat[f"{part}_count"] = snap["count"]
                lat[f"{part}_mean_s"] = round(snap["mean"], 6)
        out["latency"] = lat
        # kvwire (ISSUE 16): ship-path latency percentiles, flat under
        # the same kvwire_* prefix as the counters so the runner
        # heartbeat forwards the whole family with one startswith loop.
        # "export"/"import" are engine-side gather/splice; "ship" is the
        # transport round-trip the runner observes via note_kvwire_ship.
        for op in ("export", "import", "ship"):
            snap = summaries.get(f"tpu9_kvwire_{op}_s")
            if snap:
                out[f"kvwire_{op}_p50_s"] = round(snap["p50"], 6)
                out[f"kvwire_{op}_p95_s"] = round(snap["p95"], 6)
        # kv tiering (ISSUE 20): occupancy + paging latency percentiles,
        # flat under kvtier_* — the same one-startswith-loop heartbeat
        # contract as kvwire_*. Only emitted when a host tier exists, so
        # the untiered heartbeat is byte-identical to before.
        if self.paged and self.pool.tiered:
            ts = self.pool.tier_stats()
            out["kvtier_device_blocks"] = ts["device_blocks"]
            out["kvtier_device_bytes"] = ts["device_bytes"]
            out["kvtier_host_blocks"] = ts["host_blocks"]
            out["kvtier_host_bytes"] = ts["host_bytes"]
            out["kvtier_host_entries"] = ts["host_entries"]
            out["kvtier_host_evictions"] = ts["host_evictions"]
            out["kvtier_peer_spills"] = self.pool.peer_spills
            out["kvtier_hits_device"] = self.prefix_cache.hits_device
            out["kvtier_hits_host"] = self.prefix_cache.hits_host
            for op in ("downpage", "uppage"):
                snap = summaries.get(f"tpu9_kvtier_{op}_s")
                if snap:
                    out[f"kvtier_{op}_p50_s"] = round(snap["p50"], 6)
                    out[f"kvtier_{op}_p95_s"] = round(snap["p95"], 6)
        if self.paged:
            out["kv_blocks_used"] = self.allocator.used_count
            out["kv_blocks_free"] = self.allocator.free_count
            out["kv_blocks_reserved"] = self.allocator.reserved
            # the fleet router divides free tokens (blocks × size) into
            # an in-flight admission budget — see tpu9.router.admission
            out["kv_block_size"] = self.allocator.block_s
            # int8 pool (ISSUE 6): the free/used counts above already
            # reflect the ~2x equal-HBM pool, so the router's admission
            # math needs no change — this is observability. The MODE
            # string ("" = off), not a bool: a fleet mixing future modes
            # must be able to tell which pool format a replica runs
            out["kv_quant"] = self.ecfg.kv_quant if self.kv_quant else ""
            out["queued"] += len(self._wait_room)
            out["prefix_cache"] = self.prefix_cache.stats()
            out["prefix_tokens_hashed"] = self.prefix_cache.tokens_hashed
            # admission pressure for the router: reserved fraction is the
            # honest "can I take another request" signal under paging
            out["token_pressure"] = max(
                out["token_pressure"],
                self.allocator.reserved / max(self.allocator.n_blocks, 1))
        return out

    # -- engine loop ---------------------------------------------------------

    async def _admit_paged(self, req: _Request, slot: int):
        """Paged admission: reserve budget, reuse any cached prefix blocks,
        chunk-prefill the suffix in FUSED GROUPS of ``admit_group_chunks``
        (one forward over the group's contiguous positions per dispatch,
        splice included — VERDICT r04 #6), interleaving a decode window
        between groups so the running batch keeps producing tokens
        during a long admission. Zero host syncs here; the serve loop
        syncs the whole admission batch once. Returns the first-token
        device value."""
        from .paged_kv import blocks_for
        totals = self.host_phases
        bs = self.ecfg.kv_block_size
        n = len(req.prompt)
        with phase("engine.admit.plan", totals):
            if self._slot_blocks[slot]:
                # leftovers (defensive): return them
                self.allocator.release(self._slot_blocks[slot])
                self._slot_blocks[slot] = []
            self._slot_reserved[slot] = self.allocator.reserve(
                self._worst_case_tokens(req))

        with phase("engine.admit.lookup", totals):
            shared, p = await self._admit_lookup(req)

        with phase("engine.admit.plan", totals):
            total_blocks = blocks_for(self.cfg.kv_entries_peak(n + 1), bs)
            fresh = self._alloc_blocks(total_blocks - len(shared))
            self._slot_blocks[slot] = shared + fresh
            # the DEVICE table row stays all-trash until admission
            # completes: decode windows interleaved below scatter every
            # INACTIVE lane's write through its table row at position 0,
            # which must never be one of the blocks being spliced here
            row = np.full((self._mb,), self._trash_block, dtype=np.int32)
            row[:len(self._slot_blocks[slot])] = self._slot_blocks[slot]

        scratch = self._scratch
        if p:
            with phase("engine.admit.dispatch", totals, g=0):
                # the densified prefix REPLACES the scratch: let the old
                # one go first, or both are live at the gather's peak
                # (what it keeps by lane and by block is no gather's)
                kept = {name: a for name, a in scratch.items()
                        if name not in ("k", "v")}
                scratch = self._scratch = None
                dense = self._gather_fn()(self._pool_dict(),
                                          jnp.asarray(row))
                scratch = {**kept, "k": dense["k"], "v": dense["v"]}
                del dense
                self._stats["admit_dispatches"] += 1
                if self.graphs.restores_tails:
                    # the suffix starts from the tails as of the last row
                    # of the hit's last page
                    scratch.update(self.graphs.restore_tails(
                        self.kv_cache, int(row[p // bs - 1])))
                    self._stats["admit_dispatches"] += 1
                    self._stats["conv_tail_restores"] += 1

        with phase("engine.admit.plan", totals):
            toks_all, offsets, last_idxs, phys_all = self._chunk_tables(
                req, slot, p)
        n_chunks = len(offsets)
        self._stats["admit_chunks"] += n_chunks
        self._stats["admit_tokens"] += n - req.admit_cached
        self._stats["admit_tokens_padded"] += n_chunks * self._chunk
        if self._counts_prefix_rows:
            self._stats["prompt_rows_admitted"] += n
            self._stats["prefix_rows_reused"] += p
        if "conv_tail_blocks_written" in self._stats:
            self._stats["conv_tail_blocks_written"] += int(
                (phys_all != self._trash_block).sum())
        if self.cfg.attn_window:
            # a chunk whose first position opens a window closes the one
            # before it (its program summarises at its head)
            self._stats["windows_closed_prefill"] += int(
                ((offsets > 0) & (offsets % self.cfg.attn_window == 0)).sum())
        last = None
        group = self.graphs.group_chunks
        k_chunk = 0
        if self._keeps_routing:
            req.routed = []
        while k_chunk < n_chunks:
            # FULL groups use the wide group graph warmup compiled; a
            # partial tail (1..group-1 chunks) runs through the warmed
            # single-chunk graphs instead of JIT-compiling a fresh group
            # width mid-traffic (which would stall every active stream
            # behind an XLA compile)
            g = group if n_chunks - k_chunk >= group else 1
            sl = slice(k_chunk, k_chunk + g)
            with phase("engine.admit.dispatch", totals, g=g):
                if g > 1:
                    # a group's chunks are contiguous: one offset, and
                    # only its final chunk can be partial
                    pool, scratch, last, *picks = self._chunk_group_fn(g)(
                        self.params, self._pool_dict(), scratch,
                        jnp.asarray(toks_all[sl]),
                        int(offsets[k_chunk]),
                        int(last_idxs[k_chunk + g - 1]),
                        self._splice_blocks(phys_all, k_chunk, g, slot,
                                            int(offsets[k_chunk])))
                    self._set_pool(pool)
                    self._stats["admit_dispatches"] += 1
                    self._stats["admit_chunks_grouped"] += g
                else:
                    last, scratch, *picks = self._chunk_fn()(
                        self.params, jnp.asarray(toks_all[sl]),
                        int(offsets[k_chunk]), scratch,
                        int(last_idxs[k_chunk]))
                    self._set_pool(self._splice_fn()(
                        self._pool_dict(), scratch["k"], scratch["v"],
                        int(offsets[k_chunk]),
                        self._splice_blocks(phys_all, k_chunk, 1, slot,
                                            int(offsets[k_chunk])),
                        *self.graphs.scratch_tails(scratch)))
                    self._stats["admit_dispatches"] += 2
                if self.cfg.mla_latent:
                    # the dispatch's queries attend every row before its
                    # last (a padded tail counts: the program computes it)
                    at, width = int(offsets[k_chunk]), g * self._chunk
                    self._stats["prefill_rows_attended"] += at + width
                    self._stats["prefill_pairs_attended"] += \
                        width * at + width * (width + 1) // 2
                if picks:
                    # the dispatch's real rows: all but its last chunk's tail
                    req.routed.append((picks[0], (g - 1) * self._chunk
                                       + int(last_idxs[k_chunk + g - 1]) + 1))
            k_chunk += g
            if k_chunk < n_chunks:
                # long admission: keep the decode batch producing tokens
                # and let streaming consumers drain
                with phase("engine.window.dispatch", totals, kind="decode",
                           pick="interleave") as ph:
                    k = self._interleave_decode_window()
                    ph.set(k=k, parked=self._deferred_windows[-1].parked
                           if k else 0)
                with phase("engine.yield", totals):
                    await asyncio.sleep(0)
        self._scratch = scratch

        with phase("engine.admit.finish", totals):
            if self._lane_state_names:
                self._splice_lane_state(slot)
                self._stats["admit_dispatches"] += 1
            if self.ecfg.prefix_cache_blocks > 0:
                self.prefix_cache.insert(req.prompt,
                                         self._slot_blocks[slot],
                                         req.prefix_keys)
                req.prefix_keys = None
            self._push_table(slot)        # real row becomes visible NOW
            self.cache_len = self.cache_len.at[slot].set(n)
            self._host_len[slot] = n
            self._rng, sub = jax.random.split(self._rng)
            first = sample_logits(last, sub,
                                  temperature=self.ecfg.temperature,
                                  top_k=self.ecfg.top_k,
                                  top_p=self.ecfg.top_p)
            self.last_token = self.last_token.at[slot, 0].set(first)
            self._occupy_slot(req, slot)
        return first

    def _splice_lane_state(self, slot: int) -> None:
        """The KDA state the chunk programs left in the scratch (the whole
        prompt's, started from zero by the first chunk) becomes lane
        ``slot``'s: whatever an earlier sequence left in the lane is
        overwritten, so a reused lane starts from its own prompt alone."""
        names = self._lane_state_names
        self.kv_cache.update(self.graphs.lane_splice_fn()(
            {n: self.kv_cache[n] for n in names},
            {n: self._scratch[n] for n in names}, slot))

    async def _admit_lookup(self, req: _Request) -> tuple:
        """Prefix-cache lookup of one admission: ``(shared blocks, retained
        for the slot; cached tokens the suffix resumes behind)``. The
        prompt is hashed here, once: the walk stays on the request for
        ``engine.admit.finish``'s insert."""
        entry = None
        if self.ecfg.prefix_cache_blocks > 0:
            req.prefix_keys = self.prefix_cache.walk(req.prompt)
            entry = self.prefix_cache.lookup(req.prompt, req.prefix_keys)
        if entry is not None and entry.tier == "host":
            # host-tier hit (ISSUE 20): re-place the planes through the
            # sharding policy before the blocks can be shared. Degrades
            # to a plain miss (full recompute) if the host copy raced a
            # reap — never errors.
            entry = await self._uppage_entry(entry, req.request_id)
        shared: list[int] = list(entry.blocks) if entry else []
        p = entry.n_tokens if entry else 0
        # the suffix resumes at the hit's own page: the chunk programs take
        # their offset as an operand and mask by position, and the splice
        # addresses whole pages. One case cannot: cached prefixes land on
        # BLOCK boundaries, chunk windows are CHUNK wide, and a last window
        # that would pass max_seq_len has its start clamped backwards by
        # dynamic_update_slice, over valid prefix KV (advisor r04). Only
        # then round p down to a chunk multiple: positions [p', p) are
        # recomputed and re-spliced with bit-identical values (KV at
        # position t depends only on tokens <= t, which the cached prefix
        # shares), so overwriting the shared blocks is value-safe.
        c = self._chunk
        if p + -(-(len(req.prompt) - p) // c) * c > self.ecfg.max_seq_len:
            self._stats["prefix_rows_recomputed"] += p % c
            p -= p % c
        self.allocator.retain(shared)
        if entry is not None:
            # blocks are retained: a concurrent admission's eviction can
            # no longer free them under us — drop the lookup pin
            self.prefix_cache.release_pin(entry)
        return shared, p

    def _chunk_tables(self, req: _Request, slot: int, p: int) -> tuple:
        """Per-chunk host arrays of one admission past ``p`` cached tokens,
        built once (the former per-chunk python bookkeeping between
        dispatches was the loop's biggest host-side overhead — now it's
        one numpy pass + one transfer per group)."""
        bs = self.ecfg.kv_block_size
        c = self._chunk
        nb = c // bs
        suffix = req.prompt[p:]
        m = len(suffix)
        n_chunks = -(-m // c)
        req.admit_cached = p
        req.admit_chunks = n_chunks
        toks_all = np.zeros((n_chunks, c), dtype=np.int32)
        offsets = np.zeros((n_chunks,), dtype=np.int32)
        last_idxs = np.zeros((n_chunks,), dtype=np.int32)
        # chunk tail past the slot's blocks = padded garbage → write it to
        # the dedicated trash block, never a real one
        phys_all = np.full((n_chunks, nb), self._trash_block,
                           dtype=np.int32)
        for k_chunk, i in enumerate(range(0, m, c)):
            valid = min(c, m - i)
            toks_all[k_chunk, :valid] = suffix[i:i + valid]
            offsets[k_chunk] = p + i
            last_idxs[k_chunk] = valid - 1
            first_block = self.cfg.kv_entry(p + i) // bs
            for j in range(nb):
                idx = first_block + j
                if idx < len(self._slot_blocks[slot]):
                    phys_all[k_chunk, j] = self._slot_blocks[slot][idx]
        return toks_all, offsets, last_idxs, phys_all

    def _splice_blocks(self, phys_all, k_chunk: int, g: int, slot: int,
                       offset: int):
        """The physical blocks that the splice of ``g`` chunks from
        ``k_chunk`` on writes, as its program takes them: ``[C/BS]`` for
        one chunk, ``[g, C/BS]`` for a group. With ``attn_window`` flat and
        led by one more: the page that takes the summaries of the window
        this chunk closed — the column before the chunk's own — or the
        trash block where the chunk (at position ``offset``) opens none."""
        rows = phys_all[k_chunk] if g == 1 else phys_all[k_chunk:k_chunk + g]
        if not self.cfg.attn_window:
            return jnp.asarray(rows)
        lead = self._trash_block
        if offset and offset % self.cfg.attn_window == 0:
            lead = self._slot_blocks[slot][
                self.cfg.kv_entry(offset) // self.ecfg.kv_block_size - 1]
        return jnp.asarray(np.concatenate(
            [[lead], rows.ravel()]).astype(np.int32))

    # -- KV tiering: up-page / down-page (ISSUE 20) --------------------------

    async def _uppage_entry(self, entry, request_id: str = ""):
        """Re-place a host-tier prefix hit into fresh pool blocks through
        the sharding policy. The entry arrives PINNED from ``lookup`` and
        the pin holds for the whole up-page, so eviction pressure (a
        concurrent admission's ``evict_for_space``) can never reap it
        mid-copy. Returns the entry, device-resident and still pinned —
        or None (pin released) when the host copy was lost to a reap:
        the caller degrades to a plain recompute, never an error.

        Concurrent admissions hitting the same host entry await the
        first up-page instead of double-filling blocks."""
        cache = self.prefix_cache
        key = entry.key
        fut = self._uppage_inflight.get(key)
        if fut is not None:
            cache.release_pin(entry)
            await fut
            ent = cache._entries.get(key)
            if ent is None or ent.tier != "device":
                return None                 # primary failed: recompute
            ent.pins += 1                   # re-pin for our admission
            cache.pinned += 1
            return ent
        fut = asyncio.get_running_loop().create_future()
        self._uppage_inflight[key] = fut
        t0 = time.perf_counter()
        try:
            planes = self.pool.uppage_planes(entry)
            if planes is None:
                # the host copy vanished between advertisement and use
                # (the stale-directory window): recompute, never error
                self._stats["kvtier_uppage_failures"] += 1
                self.pool.kv_decisions.append(
                    {"decision": "recompute", "request_id": request_id,
                     "chosen": "recompute",
                     "rejected": [{"alternative": f"host:{key.hex()[:16]}",
                                   "reason": "host_copy_lost"}],
                     "signals": {"n_tokens": entry.n_tokens}})
                cache.release_pin(entry)
                if entry.pins == 0:
                    cache.drop(key, kind="evict")
                return None
            try:
                self._set_pool(self.pool.complete_uppage(
                    self._pool_dict(), entry, planes))
            except RuntimeError:
                # pool exhausted mid-up-page: the prefix stays on the
                # host tier for a calmer window; this admission simply
                # recomputes — pressure must never error a request
                self._stats["kvtier_uppage_failures"] += 1
                self.pool.kv_decisions.append(
                    {"decision": "recompute", "request_id": request_id,
                     "chosen": "recompute",
                     "rejected": [{"alternative": f"host:{key.hex()[:16]}",
                                   "reason": "pool_exhausted"}],
                     "signals": {"n_tokens": entry.n_tokens}})
                cache.release_pin(entry)
                return None
            # the scatter is dispatched, not synced: yield so the serve
            # loop can run while it lands — admission's own data deps
            # guarantee residency before the blocks are read
            with phase("engine.yield", self.host_phases):
                await asyncio.sleep(0)
            dt = time.perf_counter() - t0
            self._stats["kvtier_uppages"] += 1
            self.metrics.observe("tpu9_kvtier_uppage_s", dt)
            self.pool.kv_decisions.append(
                {"decision": "pull", "request_id": request_id,
                 "chosen": f"host:{key.hex()[:16]}",
                 "signals": {"n_tokens": entry.n_tokens,
                             "uppage_s": round(dt, 6)}})
            return entry
        except Exception:
            cache.release_pin(entry)
            raise
        finally:
            self._uppage_inflight.pop(key, None)
            if not fut.done():
                fut.set_result(True)

    def _kvtier_tick(self) -> None:
        """Window-boundary down-paging: when the scheduler's low-water
        check fires, LRU unpinned prefix entries spill to host DRAM
        *before* allocation pressure lets eviction destroy them.
        Runs only at the window boundary — the gather is a device sync
        and must never ride the per-token path."""
        quota = self.scheduler.downpage_quota()
        if not quota:
            return
        for entry in self.prefix_cache.spill_candidates(quota):
            key_hex = entry.key.hex()[:16]
            n_tok = entry.n_tokens
            t0 = time.perf_counter()
            if not self.pool.downpage(self._pool_dict(), entry):
                continue
            dt = time.perf_counter() - t0
            self._stats["kvtier_downpages"] += 1
            self.metrics.observe("tpu9_kvtier_downpage_s", dt)
            self.pool.kv_decisions.append(
                {"decision": "spill", "chosen": f"host:{key_hex}",
                 "signals": {"n_tokens": n_tok,
                             "free_blocks": self.allocator.free_count,
                             "downpage_s": round(dt, 6)}})

    # -- KV tiering: runner-facing surface (ISSUE 20) ------------------------
    # Event-loop-synchronous like the kvwire methods: pure host state.

    def kvtier_digest(self, top_k: int = 48) -> str:
        """Bounded top-K prefix-key summary for the directory heartbeat:
        ``hex16:tier:n_tokens`` comma-joined, MRU first — never the full
        key list."""
        if self.prefix_cache is None:
            return ""
        ents = sorted(self.prefix_cache._entries.values(),
                      key=lambda e: -e.last_used)[:top_k]
        return ",".join(
            f"{e.key.hex()[:16]}:{'h' if e.tier == 'host' else 'd'}"
            f":{e.n_tokens}" for e in ents)

    def kvtier_deltas(self, since: int) -> tuple:
        """Tier-change journal after cursor ``since`` (evictions/spills
        the directory must retract) + the new cursor. The runner advances
        its cursor only once a heartbeat is accepted."""
        if self.prefix_cache is None:
            return [], 0
        return self.prefix_cache.deltas_since(since)

    def drain_kv_spills(self) -> list:
        """Queued peer-cache spill payloads ``(key_hex16, payload,
        n_tokens)`` — the runner owns the transport."""
        if self.pool is None:
            return []
        return self.pool.drain_peer_spills()

    def drain_kvtier_decisions(self) -> list:
        """Journaled ``kv_tier`` decision dicts (spill/pull/recompute/
        evict choices made inside the serving plane). The runner records
        them into the decision ledger — the one-way evidence flow BND001
        pins (serving must not import the ledger). Destructive read."""
        if self.pool is None or not self.pool.kv_decisions:
            return []
        out = list(self.pool.kv_decisions)
        self.pool.kv_decisions.clear()
        return out

    # -- observability hooks (ISSUE 8) ---------------------------------------
    # All host-side bookkeeping on state the loop already holds: monotonic
    # durations, per-engine metric observes (per request / per window,
    # never per token), and — only for requests carrying a remote trace
    # context — span records into the process tracer ring the runner ships
    # on its pressure heartbeat.

    def _obs_admit_start(self, req: _Request, t0_mono: float,
                         t0_wall: float) -> None:
        anchor = (req.t_enqueue_wall, req.t_enqueue_mono)
        if req.trace is not None:
            topo = self.policy.describe()
            req.span = tracer.start_span(
                "engine.request", trace_id=req.trace[0],
                parent_id=req.trace[1],
                attrs={"request_id": req.request_id,
                       "prompt_tokens": len(req.prompt),
                       "max_new_tokens": req.max_new_tokens,
                       # multichip evidence rides the PR-8 observability
                       # layer (ISSUE 9): which submesh served this request
                       "tp": topo["tp"], "n_chips": topo["n_chips"]})
            req.span_id = req.span.span_id
            # backdate to the enqueue anchor: the request span covers
            # queue-wait + prefill + every decode window
            req.span.start, req.span.start_mono = anchor
        tracer.record_interval(
            "engine.queue_wait", self.metrics, "tpu9_engine_queue_wait_s",
            anchor, req.t_enqueue_mono, t0_mono, trace=self._under(req),
            attrs={"request_id": req.request_id})

    @staticmethod
    def _under(req: _Request) -> Optional[tuple]:
        """The trace context of a child of ``engine.request``, or None for
        a request that carries no trace."""
        return (req.trace[0], req.span_id) if req.span_id else None

    def _obs_admit_end(self, req: _Request, t0_mono: float, t0_wall: float,
                       il0: int) -> None:
        req.t_admit_end_mono = time.monotonic()
        self._last_progress_mono = req.t_admit_end_mono   # = progress
        interleaved = self._stats["admit_interleaved_windows"] - il0
        dur = tracer.record_interval(
            "engine.prefill", self.metrics, "tpu9_engine_prefill_s",
            (t0_wall, t0_mono), t0_mono, req.t_admit_end_mono,
            trace=self._under(req),
            attrs={"request_id": req.request_id,
                   "prompt_tokens": len(req.prompt),
                   "cached_tokens": req.admit_cached,
                   "chunks": req.admit_chunks,
                   "interleaved_windows": interleaved})
        if self.flight is not None:
            self.flight.record(
                "admit", request_id=req.request_id, slot=req.slot,
                prompt_tokens=len(req.prompt),
                cached_tokens=req.admit_cached, chunks=req.admit_chunks,
                interleaved=interleaved, dur_s=round(dur, 6))

    def _obs_stamp_window(self, win: _Window) -> _Window:
        win.t_mono = time.monotonic()
        win.t_wall = time.time()
        # liveness watermark (ISSUE 14): the watchdog's "did the loop
        # still reach a dispatch" stamp
        self._last_dispatch_mono = win.t_mono
        win.gap_clock0 = self._admit_clock(win.t_mono)
        win.pick = self._pick_reason
        if self.paged:
            win.kv_snap = (self.allocator.used_count,
                           self.allocator.free_count,
                           self.allocator.reserved)
        return win

    def _obs_window(self, win: _Window, t_host0: float) -> None:
        """One flight record at host processing time, and for each traced
        request the window's share of its ONE ``engine.decode`` span,
        recorded once the request has retired (per-window detail lives in
        the flight record and the ``engine.window.*`` phases). ``wait_s``
        (dispatch → fan-out start) includes the deliberate one-window
        overlap; ``host_s`` is the fan-out. ``period_s`` is what a running
        stream sees of the window (``_obs_gap``)."""
        now_m = time.monotonic()
        self.metrics.observe("tpu9_engine_decode_window_s",
                             max(t_host0 - win.t_mono, 0.0))
        # liveness watermark (ISSUE 14): a host-processed window IS
        # progress — the counter the watchdog requires to keep moving
        # while work is queued
        self._windows_processed += 1
        self._last_progress_mono = now_m
        delivered = win.delivered or {}
        self._obs_gap(win, t_host0, delivered)
        if self.flight is not None:
            slots = {s: r.request_id
                     for s, r in enumerate(win.reqs)
                     if r is not None and win.mask[s]}
            rec = {"k": win.k, "pick": win.pick,
                   "batch": int(win.mask.sum()),
                   "parked": win.parked,
                   "slots": slots, "tokens": delivered,
                   "wait_s": round(max(t_host0 - win.t_mono, 0.0), 6),
                   "host_s": round(max(now_m - t_host0, 0.0), 6),
                   "period_s": round(win.period_s, 6),
                   "admit_s": round(win.admit_s, 6),
                   "lanes": len(delivered)}
            topo = self.policy.describe()
            if topo["n_chips"] > 1:
                # stamp the submesh onto multichip window records only —
                # 1x1 flight records stay byte-identical to the pre-split
                # engine's
                rec.update(tp=topo["tp"], n_chips=topo["n_chips"])
            if win.kind == "verify":
                prop, acc = win.spec_stats or (0, 0)
                rec.update(spec_proposed=prop, spec_accepted=acc,
                           spec_rollback=prop - acc,
                           spec_len=win.spec_len)
            if win.kv_snap:
                used, free, reserved = win.kv_snap
                rec.update(kv_used=used, kv_free=free, kv_reserved=reserved,
                           kv_alloc=self.pool.kv_allocs
                           - self._flight_kv_allocs)
                self._flight_kv_allocs = self.pool.kv_allocs
                if self.prefix_cache is not None:
                    ev = self.prefix_cache.evictions
                    rec.update(
                        prefix_evictions=ev - self._flight_evictions,
                        prefix_pinned=self.prefix_cache.pinned)
                    self._flight_evictions = ev
            self.flight.record(win.kind, **rec)
        for slot, req in enumerate(win.reqs):
            if req is None or not win.mask[slot]:
                continue
            n_tok = delivered.get(slot, 0)
            if n_tok > 0 and req.span_id:
                if req.dec is None:
                    req.dec_anchor = (win.t_wall, win.t_mono)
                    req.dec = {"request_id": req.request_id, "windows": 0,
                               "k1_windows": 0, "tokens": 0,
                               "interleaved_windows": 0, "parked_steps": 0}
                    if self.cfg.looped:
                        req.dec["loop_steps"] = self.cfg.loop_steps
                req.dec["windows"] += 1
                req.dec["k1_windows"] += win.k == 1
                req.dec["tokens"] += n_tok
                req.dec["interleaved_windows"] += win.pick == "interleave"
                req.dec["parked_steps"] += win.k - int(win.steps[slot])
            if req.gap_last and req.done.is_set():
                # retired inside this window's fan-out (or, cancelled,
                # before it): its decode interval is complete
                self._obs_stream_gap(req)

    def _admit_clock(self, t_mono: float) -> float:
        """The admission clock at a stamp: the seconds the serve loop has
        spent in admission episodes, the open one up to the stamp."""
        if self._admit_open_mono:
            return self._admit_clock_s + (t_mono - self._admit_open_mono)
        return self._admit_clock_s

    def _gap_marks(self, t_mono: float) -> tuple:
        """A delivery stamp with the admission clock at it, and the
        admissions begun by then. Differences of two stamps' marks are a
        period, its admit part, and the admissions that fell inside it."""
        return (t_mono, self._admit_clock(t_mono),
                self._stats["gap_admissions"])

    def _obs_gap(self, win: _Window, t_host0: float, delivered: dict) -> None:
        """A window's period as its lanes see it (ISSUE 57). A running
        stream gets tokens only here, so the time since a lane's delivery
        before — the window before, for every lane that window also
        delivered to: ONE period they share; its own first token, for a
        lane that joined since — is the gap its tokens waited, and the
        admission clock's movement inside it the part that admissions of
        other requests took. Once a window, two attributes a lane; nothing
        a token, no clock read (``t_host0`` is the fan-out's own)."""
        marks, prev = self._gap_marks(t_host0), self._gap_prev
        if prev:
            win.period_s = marks[0] - prev[0]
            win.admit_s = marks[1] - prev[1]
        if not delivered:
            return
        shared = 0
        joined_s = joined_admit_s = 0.0
        for slot in delivered:
            req = win.reqs[slot]
            last = req.gap_last or req.gap_first or marks
            if last is prev:
                shared += 1
            else:
                joined_s += marks[0] - last[0]
                joined_admit_s += marks[1] - last[1]
            req.gap_max = max(req.gap_max, marks[0] - last[0])
            req.gap_last = marks
        st = self._stats
        st["gap_tokens"] += sum(delivered.values())
        st["gap_lane_period_s"] += shared * win.period_s + joined_s
        st["gap_lane_admit_s"] += shared * win.admit_s + joined_admit_s
        st["gap_lane_steps"] += len(delivered) * win.k
        # no admission touched it: the clock has stood still since the
        # delivery before AND since its own dispatch (a window interleaved
        # inside an admission is fanned out after it, behind another). Its
        # period over its steps is the step its lanes saw; what the lanes
        # of the other windows saw beyond that a step is what admissions
        # cost them, the decode windows interleaved inside an admission
        # taken off (``tpot_admit_stall_ms``). Weighed by lanes, as a
        # stream's own gap is.
        if shared and marks[1] == min(prev[1], win.gap_clock0):
            win.clean = True
            st["gap_clean_lane_period_s"] += shared * win.period_s
            st["gap_clean_lane_steps"] += shared * win.k
        self._gap_prev = marks

    def _obs_stream_gap(self, req: _Request) -> None:
        """Once a request, when the window that retired it is fanned out:
        the engine's own time per output token, (last delivery - first
        token) / (tokens - 1), the summary ``tpu9_engine_tpot_s`` and the
        ONE ``engine.decode`` span of a traced request; the largest period
        it sat through, ``tpu9_engine_gap_max_s``."""
        first, last, req.gap_last = req.gap_first, req.gap_last, ()
        n = len(req.generated)
        if n < 2:
            return
        attrs, req.dec = req.dec, None
        if attrs is not None:
            attrs.update(
                gap_mean_ms=round((last[0] - first[0]) / (n - 1) * 1e3, 3),
                gap_max_ms=round(req.gap_max * 1e3, 3),
                admit_stall_ms=round((last[1] - first[1]) * 1e3, 3),
                admissions_behind=last[2] - first[2])
        tracer.record_interval(
            "engine.decode", self.metrics, "tpu9_engine_tpot_s",
            req.dec_anchor, first[0], last[0], trace=self._under(req),
            attrs=attrs, per=n - 1)
        self.metrics.observe("tpu9_engine_gap_max_s", req.gap_max)

    def _obs_first_token(self, req: _Request) -> None:
        """TTFT, and its last part: the hold between the end of a request's
        admission and the delivery of its first token (the first tokens of
        a batch of admissions sync together after the last)."""
        req.t_first_mono = time.monotonic()
        self.metrics.observe(
            "tpu9_engine_ttft_s",
            max(req.t_first_mono - req.t_enqueue_mono, 0.0))
        if req.t_admit_end_mono:
            tracer.record_interval(
                "engine.first_hold", self.metrics,
                "tpu9_engine_first_hold_s",
                (req.t_enqueue_wall, req.t_enqueue_mono),
                req.t_admit_end_mono, req.t_first_mono,
                trace=self._under(req),
                attrs={"request_id": req.request_id})

    # The runner's leg (ISSUE 41), fed by the SSE handler beside the
    # engine's own: intervals of the handler's clock, which is this
    # process's, so they nest around the engine's parts with no clock
    # shared between hosts. Once a request; nothing a token.

    def note_ingest(self, req: _Request, anchor: tuple, t_enqueued: float,
                    t_ready: float) -> None:
        """``anchor``: the handler's (wall, monotonic) pair at its first
        line; ``t_enqueued``: ``generate`` has returned ``req``;
        ``t_ready``: the response's headers are written. ``ingest`` is
        first line -> headers: body read, ``json.loads``, the ``int()``
        loop, the enqueue, ``prepare``."""
        req.ingest_anchor, req.t_ready_mono = anchor, t_ready
        tracer.record_interval(
            "runner.ingest", self.metrics, "tpu9_engine_ingest_s", anchor,
            anchor[1], t_ready, trace=req.trace,
            attrs={"request_id": req.request_id,
                   "prompt_tokens": len(req.prompt),
                   "parse_s": round(max(t_enqueued - anchor[1], 0.0), 6)})

    def note_first_write(self, req: _Request) -> None:
        """Fed by the runner when its handler has written a request's
        first token to the client. Stream lag (ISSUE 24): from the first
        token's queue put to here — the wait for the event loop the serve
        loop shares. ``runner_first`` (ISSUE 41): from the headers to
        here — queue wait + admission + hold + stream lag, less what of
        the enqueue lay before the headers; ``ttft`` + ``stream_lag`` is
        its check."""
        now = req.t_first_write_mono = time.monotonic()
        if req.t_first_mono:
            self.metrics.observe(
                "tpu9_engine_stream_lag_s",
                max(now - req.t_first_mono, 0.0))
        if req.t_ready_mono:
            tracer.record_interval(
                "runner.first_token", self.metrics,
                "tpu9_engine_runner_first_s", req.ingest_anchor,
                req.t_ready_mono, now, trace=req.trace,
                attrs={"request_id": req.request_id})

    def note_last_write(self, req: _Request, t_last: float) -> None:
        """Fed by the runner when a request's stream has ended without an
        error, with the stamp at which its handler had written the last
        token: the runner's own time per output token (ISSUE 57), (last
        write - first write) / (tokens - 1) — the engine's, plus what the
        handler and the event loop it shares with the serve loop put
        between two writes. Told once a request."""
        t_first, req.t_first_write_mono = req.t_first_write_mono, 0.0
        n = len(req.generated)
        if t_first and n >= 2 and req.ingest_anchor:
            tracer.record_interval(
                "runner.stream", self.metrics, "tpu9_engine_runner_gap_s",
                req.ingest_anchor, t_first, t_last, trace=req.trace,
                attrs={"request_id": req.request_id, "tokens": n},
                per=n - 1)

    def _obs_done(self, req: _Request) -> None:
        """Idempotent: reachable from both _retire (slot completion) and
        _finish (error/cancel paths) — only the FIRST call observes."""
        now = time.monotonic()
        n = len(req.generated)
        if req.t_enqueue_mono:
            req.t_done_mono = now
            self.metrics.observe("tpu9_engine_e2e_s",
                                 max(now - req.t_enqueue_mono, 0.0))
            req.t_enqueue_mono = 0.0
        if req.span is not None:
            sp, req.span = req.span, None     # exactly one finish per span
            sp.attrs["tokens_generated"] = n
            tracer.finish_span(sp, status="error" if req.error else "ok")

    # -- on-demand profiling (ISSUE 8) ---------------------------------------

    def arm_profile(self, seconds: float = 4.0, out_dir: str = "") -> dict:
        """Trace the next ``seconds`` seconds with ``jax.profiler``, from a
        worker thread: returns the dump path at once and never holds the
        serve loop (writing out a trace of four chips takes minutes). The
        options are the benchmark's, so the operator's dump is the trace
        its readers take: device planes, and on the host plane the serve
        loop's phases. ``device_scopes.json`` beside it maps each
        program's HLO instructions to the model's scopes."""
        if not seconds > 0:
            raise ValueError(f"seconds must be positive, got {seconds}")
        if self._profile["active"]:
            return {**self._profile, "already_armed": True}
        import tempfile
        path = out_dir or tempfile.mkdtemp(prefix="tpu9-profile-")
        self._profile = {"active": True, "path": path,
                         "seconds": float(seconds), "error": ""}
        self._profile_cut.clear()
        if self.flight is not None:
            self.flight.record("profile", event="armed", seconds=seconds,
                               path=path)
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        self._profile_thread = threading.Thread(
            target=self._profile_run, args=(path, float(seconds), loop),
            name="tpu9-profile", daemon=True)
        self._profile_thread.start()
        return {"path": path, "seconds": float(seconds)}

    def _profile_run(self, path: str, seconds: float, loop) -> None:
        """The profile thread's body. Profiling must never take the serve
        loop down: a failure lands in ``stats()["profile"]["error"]``."""
        error, t0 = "", time.monotonic()
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # millions of events otherwise
            options.host_tracer_level = 1     # TraceAnnotation: the phases
            jax.profiler.start_trace(path, profiler_options=options)
            try:
                self._profile_cut.wait(seconds)
                traced = time.monotonic() - t0
            finally:
                jax.profiler.stop_trace()
            if self.graphs.device_scopes:
                import json
                import os
                with open(os.path.join(path, "device_scopes.json"),
                          "w") as f:
                    json.dump(self.graphs.device_scopes, f)
        except Exception as exc:    # noqa: BLE001 — see the docstring
            error, traced = f"{type(exc).__name__}: {exc}", 0.0

        def done():
            self._profile = {**self._profile, "active": False,
                             "error": error}
            if self.flight is not None:
                self.flight.record(
                    "profile", event="stopped", path=path,
                    traced_s=round(traced, 3), error=error,
                    dump_s=round(time.monotonic() - t0 - traced, 3))

        try:    # on the loop that armed it, if that loop still runs
            loop.call_soon_threadsafe(done)
        except (AttributeError, RuntimeError):
            done()

    def _occupy_slot(self, req: _Request, slot: int) -> None:
        req.slot = slot
        self.active[slot] = True
        self.slot_req[slot] = req
        if self._spec_lens:
            from .spec import make_slot_state
            self._spec_slots[slot] = make_slot_state(req.prompt)

    def _interleave_decode_window(self) -> int:
        """Dispatch one decode window for the active batch WITHOUT syncing
        (results processed after the admission sync). Returns the window's
        steps (0: none dispatched)."""
        if not self.active.any():
            return 0
        ks = self.ecfg.decode_steps
        want = ks[1] if len(ks) > 1 else ks[0]
        # the steps in flight inside one admission stay within
        # max(decode_steps): how many interleave is the trade between this
        # admission's first token and the running streams' gap. The lanes'
        # numbers are the classic window's (``WindowScheduler.lane_steps``):
        # the lane with the most left decides, the program parks the rest
        slack = max(ks) - self._inflight_steps
        left = self.scheduler.lane_steps()
        k = self.scheduler.bucket_within(
            min(want, slack, int(left.max(initial=0))))
        if k <= 0:
            return 0      # no slack, or every lane's last steps are in flight
        self._pick_reason = "interleave"
        self._deferred_windows.append(self._run_decode_window(k, left))
        self._stats["admit_interleaved_windows"] += 1
        return k

    async def _admit(self, req: _Request, slot: int):
        """Prefill + cache splice for one request. Returns the slot's
        first-token DEVICE value — the serve loop syncs a whole admission
        batch in one host round-trip (each blocking ``int()`` here would
        cost a full one)."""
        t0_mono, t0_wall = time.monotonic(), time.time()
        with phase("engine.admit", self.host_phases,
                   request_id=req.trace[0] if req.trace else req.request_id,
                   prompt_tokens=len(req.prompt)) as ph:
            self._obs_admit_start(req, t0_mono, t0_wall)
            # the admission clock runs from the first admission of a pass
            # of the serve loop to the end of its ``deliver_first``
            if not self._admit_open_mono:
                self._admit_open_mono = t0_mono
            self._stats["gap_admissions"] += 1
            il0 = self._stats["admit_interleaved_windows"]
            if self.paged:
                first = await self._admit_paged(req, slot)
            else:
                with phase("engine.admit.dispatch", self.host_phases):
                    first = self._admit_dense(req, slot)
            self._obs_admit_end(req, t0_mono, t0_wall, il0)
            ph.set(cached_tokens=req.admit_cached, chunks=req.admit_chunks)
        return first

    def _admit_dense(self, req: _Request, slot: int):
        n = len(req.prompt)
        bucket = self._bucket_for(n)
        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, :n] = req.prompt[:bucket]
        last, cache = self._prefill_fn(bucket)(
            self.params, jnp.asarray(tokens), n)
        # copy prefix cache into the slot's lanes — jitted + donated: the
        # eager form copied the whole [L,B,S,KH,D] cache twice per
        # admission (GBs of HBM traffic + a transient second allocation)
        self.kv_cache["k"], self.kv_cache["v"] = self._dense_splice_fn(
            bucket)(self.kv_cache["k"], self.kv_cache["v"],
                    cache["k"], cache["v"], slot)
        self.cache_len = self.cache_len.at[slot].set(n)
        self._host_len[slot] = n
        # sample the first generated token from the prefill logits
        self._rng, sub = jax.random.split(self._rng)
        first = sample_logits(last, sub, temperature=self.ecfg.temperature,
                              top_k=self.ecfg.top_k, top_p=self.ecfg.top_p)
        self.last_token = self.last_token.at[slot, 0].set(first)
        self._occupy_slot(req, slot)
        return first

    def _deliver_first(self, req: _Request, first: int) -> None:
        req.generated.append(first)
        if req.routed:
            # the prompt's chosen experts come to the host with its first
            # token (the prefill programs are done: ordered before it)
            # tpu9: noqa[JAX001] finished arrays, read once a request beside its first token
            parts = jax.device_get([a for a, _ in req.routed])
            req.routed = [a[:n] for a, (_, n) in zip(parts, req.routed)]
        self._obs_first_token(req)
        st = self._spec_slots[req.slot] if req.slot >= 0 else None
        if st is not None:
            st.proposer.append(first)
        if req.queue is not None:
            req.queue.put_nowait(first)
        # the prefill-sampled token may already satisfy the stop conditions
        if (req.max_new_tokens <= 1
                or (self.ecfg.eos_id >= 0 and first == self.ecfg.eos_id)):
            self._retire(req.slot)

    def _retire(self, slot: int) -> None:
        req = self.slot_req[slot]
        self.active[slot] = False
        self.slot_req[slot] = None
        self._spec_slots[slot] = None
        self.cache_len = self.cache_len.at[slot].set(0)
        self._host_len[slot] = 0
        if self.paged:
            # physical blocks back to the pool (prefix-cache refs keep
            # shared prefix blocks alive), worst-case reservation released
            self.kv_cache["table"] = self.pool.release_slot(slot)
        if req is not None:
            if req.routed is not None and req.generated and not req.error:
                from . import routed_experts
                routed_experts.note(req.prompt, req.generated, req.routed,
                                    req.admit_cached)
            self._obs_done(req)
            if req.queue is not None:
                req.queue.put_nowait(None)
            req.done.set()

    def _room_for(self, req: _Request) -> bool:
        """Paged admission control: a request enters only when the pool can
        reserve its worst case — so mid-decode allocation can never fail."""
        return (not self.paged
                or self.allocator.can_reserve(self._worst_case_tokens(req)))

    @staticmethod
    def _req_expired(req: "_Request") -> bool:
        return (req.deadline_mono > 0
                and time.monotonic() > req.deadline_mono)

    def _expire_unadmitted(self, req: "_Request") -> None:
        """Deadline expiry BEFORE prefill (ISSUE 15): the whole point of
        admission-side deadlines — chips never prefill an answer the
        client has already stopped waiting for."""
        self._stats["deadline_expired"] += 1
        self._finish(req, error=f"{DEADLINE_ERROR}: budget exhausted "
                                "before prefill")

    def _next_admittable(self) -> Optional[_Request]:
        while self.paged and self._wait_room:
            head = self._wait_room[0]
            if head.cancelled or self._req_expired(head):
                self._wait_room.pop(0)
                if head.cancelled:
                    self._finish(head)
                else:
                    self._expire_unadmitted(head)
                continue
            if self._room_for(head):
                return self._wait_room.pop(0)
            return None                     # FIFO: don't starve the head
        while not self._queue.empty():
            req = self._queue.get_nowait()
            if req.cancelled:
                self._finish(req)
                continue
            if self._req_expired(req):
                self._expire_unadmitted(req)
                continue
            if self._room_for(req):
                return req
            self._wait_room.append(req)
            return None
        return None

    def _finish(self, req: _Request, error: str = "") -> None:
        if error and not req.error:
            req.error = error
        self._obs_done(req)
        if req.queue is not None:
            req.queue.put_nowait(None)
        req.done.set()

    def _fail_all_requests(self, reason: str) -> None:
        """Give every known request a terminal answer: admitted slots, the
        one mid-admission, the wait room, and the queue. A caller left
        awaiting a dead engine hangs forever."""
        for req in ([r for r in self.slot_req if r is not None]
                    + ([self._admitting] if self._admitting else [])
                    + list(self._wait_room)):
            self._finish(req, error=reason)
        self._wait_room.clear()
        self._admitting = None
        while not self._queue.empty():
            self._finish(self._queue.get_nowait(), error=reason)

    async def _serve_loop(self) -> None:
        try:
            await self._serve_loop_inner()
        except asyncio.CancelledError:
            raise
        except Exception as exc:      # noqa: BLE001
            # a dead loop must not leave callers awaiting forever — fail
            # every known request with the cause, and make generate()
            # fail FAST from now on (the loop is never restarted; the
            # runner's health surface flips on engine_dead)
            logging.getLogger("tpu9.serving").exception("engine loop died")
            self._dead_reason = f"{type(exc).__name__}: {exc}"
            # black box FIRST (ISSUE 14): _fail_all_requests clears the
            # scheduler state the record exists to capture. A crashing
            # snapshot must never mask the original failure.
            try:
                self.last_postmortem = self.blackbox(
                    "engine_crash", f"{type(exc).__name__}: {exc}")
            except Exception:   # noqa: BLE001 — evidence is best-effort
                logging.getLogger("tpu9.serving").exception(
                    "post-mortem snapshot failed")
            self._fail_all_requests(f"engine failure: {exc}")
            raise

    async def _serve_loop_inner(self) -> None:
        totals = self.host_phases
        while True:
            # admit as many queued requests as there are free slots; ALL
            # their first tokens sync in one device round-trip at the end.
            # An imminent admission first drains the steady-state overlap
            # window: its steps occupy the reservation slack the
            # admission-interleaved decode windows need, and its
            # retirements may free the very slot being admitted into.
            if self._deferred_windows and self._admission_can_proceed():
                self._drain_windows()
            pending: list[tuple[_Request, Any]] = []
            while not self.active.all():
                req = self._next_admittable()
                if req is None:
                    break
                slot = int(np.argmin(self.active))
                self._admitting = req       # failure fan-out must see it
                pending.append((req, await self._admit(req, slot)))
                self._admitting = None

            if not self.active.any() and not pending:
                if self.paged and self._wait_room:
                    # engine idle with a waiting head means reservations
                    # are zero, so the ONLY way it can't admit is being
                    # bigger than the whole pool — fail it loudly (prefix-
                    # cache pressure is handled inside _alloc_blocks)
                    head = self._wait_room.pop(0)
                    head.error = "request exceeds KV pool capacity"
                    if head.queue is not None:
                        head.queue.put_nowait(None)   # release SSE readers
                    head.done.set()
                    continue
                if self._deferred_windows:
                    # a zombie overlap window (its slots all retired during
                    # the previous iteration's drain, with this successor
                    # already in flight): process it BEFORE parking, or its
                    # device work goes unaccounted
                    self._drain_windows()
                # idle: block for work
                with phase("engine.park", totals):
                    req = await self._queue.get()
                if req.cancelled:
                    self._finish(req)
                    continue
                if self._req_expired(req):
                    self._expire_unadmitted(req)
                    continue
                if not self._room_for(req):
                    self._wait_room.append(req)
                    continue
                self._admitting = req
                pending.append((req, await self._admit(req, 0)))
                self._admitting = None

            if pending:
                with phase("engine.first_sync", totals, n=len(pending)):
                    # tpu9: noqa[JAX001] intended sync point: ONE batched read of all admitted prefill first-tokens (TTFT requires delivering them now)
                    firsts = np.asarray(jax.device_get(
                        jnp.stack([f for _, f in pending])))
                with phase("engine.deliver_first", totals):
                    for (req, _), first in zip(pending, firsts):
                        self._deliver_first(req, int(first))
                self._admit_clock_s += \
                    time.monotonic() - self._admit_open_mono
                self._admit_open_mono = 0.0
                # a stream's decode interval opens at its first token, with
                # the clock as the episode that admitted it leaves it: what
                # the clock moves by from here on is other requests'
                for req, _ in pending:
                    req.gap_first = self._gap_marks(req.t_first_mono)
                # windows dispatched during those admissions: their tokens
                # are ready by now (device work ordered before firsts) —
                # drain them in one transfer
                self._drain_windows()

            if not self.active.any():
                # retirements can only land at host processing: leftover
                # in-flight windows must drain before the idle block
                if self._deferred_windows:
                    self._drain_windows()
                continue

            # window boundary: down-page LRU prefixes to host DRAM when
            # the pool nears eviction pressure (ISSUE 20; no-op untiered)
            if self.paged and self.pool.tiered:
                with phase("engine.kvtier_tick", totals):
                    self._kvtier_tick()
            # one WINDOW for the whole batch — speculative verify when the
            # acceptance EWMAs justify it, classic k-step decode otherwise
            with phase("engine.window.dispatch", totals) as ph:
                win = self._dispatch_window()
                if win is not None:
                    ph.set(kind=win.kind, k=win.k, pick=win.pick,
                           batch=int(win.mask.sum()),
                           parked=win.parked)
            if win is not None:
                self._deferred_windows.append(win)
                # steady-state overlap (ISSUE 5 satellite): keep exactly
                # ONE window in flight — the host fan-out of every older
                # window runs WHILE the new one computes on device,
                # instead of serializing host work behind each sync
                while len(self._deferred_windows) > 1:
                    self._process_deferred(self._deferred_windows.pop(0))
            # yield to the event loop so new requests can land
            with phase("engine.yield", totals):
                await asyncio.sleep(0)

    # -- window dispatch / processing ---------------------------------------

    def _dispatch_window(self) -> Optional[_Window]:
        s = self._spec_room_len()
        if s > 0:
            s = self._spec_gate(s)
        if s > 0:
            # drafts must continue the DELIVERED history: drain any
            # in-flight window first so the proposers' view matches the
            # device last_token (classic windows keep the overlap; a
            # verify window instead amortizes the sync over up to 1+s
            # tokens per slot)
            while self._deferred_windows:
                self._process_deferred(self._deferred_windows.pop(0))
            if not self.active.any():
                return None
            from .spec import build_drafts
            drafts, n_real = build_drafts(self._spec_slots, self.active, s)
            if int(n_real.sum()) > 0:
                return self._dispatch_verify(s, drafts, n_real)
            # nothing to propose anywhere: a verify pass would be a pure
            # waste — fall through to a classic window
        left = self.scheduler.lane_steps()
        if not left.any() and self._deferred_windows:
            # every live lane's last steps are in flight: there is nothing
            # to run until their fan-out has retired the lanes
            self._drain_windows()
            if not self.active.any():
                return None
            left = self.scheduler.lane_steps()
        return self._run_decode_window(self._pick_steps(left), left)

    def _run_decode_window(self, k: int, left) -> _Window:
        """Dispatch ONE decode window of ``k`` steps for the active batch,
        without syncing: lane ``b`` runs ``min(k, left[b])`` of them
        (``left``: ``WindowScheduler.lane_steps``) and the program parks it
        for the rest."""
        given = np.minimum(left, k).astype(np.int32)
        if self.paged:
            # lazy physical growth: each active slot gets blocks for the
            # rows it writes in this window (covered by its reservation) —
            # its steps' own, and the one a parked lane's step lands on
            # past them. Clamp to max_seq_len: a lane's number bounds its
            # positions to the cache, and a near-full slot must not demand
            # a 17th block of a 16-wide table.
            for slot in map(int, np.flatnonzero(self.active)):
                self._ensure_slot_blocks(
                    slot, min(int(self._host_len[slot])
                              + int(self._lane_inflight[slot])
                              + int(given[slot]) + 1,
                              self.ecfg.max_seq_len))
        self._note_decode_entries(k, given)
        # the window's own copies: the device may read the host's buffers
        # where they lie (the CPU backend), after ``active`` has moved on
        mask = self.active.copy()
        (self.last_token, self.kv_cache,
         self.cache_len, self._rng, toks, *exits) = self._decode_k(k)(
            self.params, self.kv_cache, self.last_token,
            self.cache_len, jnp.asarray(given), self._rng)
        self._stats["decode_steps"] += k
        parked = int((k - given[mask]).sum())
        self._stats["decode_lane_steps_parked"] += parked
        return self._window_in(self._obs_stamp_window(
            _Window(kind="decode", k=k, toks=toks, mask=mask, steps=given,
                    parked=parked, reqs=tuple(self.slot_req),
                    **self._beside_tokens(exits))))

    def _window_in(self, win: _Window) -> _Window:
        """A dispatched window's steps are in flight until its fan-out."""
        self._inflight_steps += win.k
        self._lane_inflight += win.steps
        return win

    def _window_out(self, win: _Window) -> None:
        self._inflight_steps -= win.k
        self._lane_inflight -= win.steps

    def _dispatch_verify(self, s: int, drafts, n_real) -> _Window:
        t = s + 1
        if self.paged:
            for slot in range(self.ecfg.max_batch):
                if self.active[slot]:
                    self._ensure_slot_blocks(
                        slot, min(int(self._host_len[slot]) + t + 1,
                                  self.ecfg.max_seq_len))
        mask = self.active.copy()       # the window's own, as in decode
        (self.last_token, self.kv_cache, self.cache_len, self._rng, out,
         n_acc) = self._verify_fn(s)(
            self.params, self.kv_cache, self.last_token,
            jnp.asarray(drafts), self.cache_len, jnp.asarray(mask),
            self._rng)
        self._stats["spec_windows"] += 1
        self._pick_reason = "spec"
        # (a lane advances by 1 .. t: until the fan-out, count them all)
        return self._window_in(self._obs_stamp_window(
            _Window(kind="verify", k=t, toks=out, n_acc=n_acc,
                    mask=mask, steps=(mask * t).astype(np.int32),
                    reqs=tuple(self.slot_req),
                    spec_len=s, n_real=n_real)))

    def _drain_windows(self) -> None:
        """Host-process every in-flight window. ONE transfer for all of
        them — N sequential device_gets would pay N round-trips."""
        wins, self._deferred_windows = self._deferred_windows, []
        if not wins:
            return
        with phase("engine.window.sync", self.host_phases,
                   windows=len(wins)):
            # tpu9: noqa[JAX001] intended sync point: the ONE batched window-boundary device_get (PR 5); N sequential reads would pay N round-trips
            payload = jax.device_get([self._window_arrays(w) for w in wins])
        for w, arrs in zip(wins, payload):
            self._window_out(w)
            self._process_window_host(
                w, np.asarray(arrs[0]),  # tpu9: noqa[JAX001] arrs are already host memory (device_get above); asarray is a no-copy view
                np.asarray(arrs[1]) if len(arrs) > 1 else None)  # tpu9: noqa[JAX001] host memory, no device sync

    def _beside_tokens(self, rest) -> dict:
        """The field of a decode window that holds what its program returns
        beside the tokens: a looped decoder's exit passes, a layer pattern's
        chosen experts — or a plain expert decoder's, where a step reads the
        experts its live lanes picked — nothing for a dense decoder."""
        if not rest:
            return {}
        return {"exits" if self.cfg.looped else "picks": rest[0]}

    @staticmethod
    def _window_arrays(win: _Window) -> tuple:
        """What the host fetches of a window: its tokens, and the ONE array
        that may ride beside them — a verify window's accepted counts, or on
        a decode window a looped decoder's exit passes and pass counts or a
        layer pattern's chosen experts."""
        second = win.n_acc if win.kind == "verify" else \
            win.picks if win.exits is None else win.exits
        return (win.toks,) if second is None else (win.toks, second)

    def _process_deferred(self, win: _Window) -> None:
        with phase("engine.window.sync", self.host_phases, windows=1):
            # tpu9: noqa[JAX001] intended sync point: the window's compute is DONE (one-window-overlap drains here); this ONE batched read is the host fan-out
            arrs = jax.device_get(self._window_arrays(win))
        self._window_out(win)
        self._process_window_host(
            win, np.asarray(arrs[0]),  # tpu9: noqa[JAX001] host memory after device_get, no sync
            np.asarray(arrs[1]) if len(arrs) > 1 else None)  # tpu9: noqa[JAX001] host memory after device_get, no sync

    def _deliver_token(self, slot: int, tok: int) -> None:
        """Deliver ONE generated token to the slot's request, retiring the
        slot when it satisfies a stop condition (budget / EOS / cache
        room)."""
        req = self.slot_req[slot]
        req.generated.append(tok)
        self._host_len[slot] += 1
        self._stats["tokens_generated"] += 1
        st = self._spec_slots[slot]
        if st is not None:
            st.proposer.append(tok)
        if req.queue is not None:
            req.queue.put_nowait(tok)
        hit_eos = self.ecfg.eos_id >= 0 and tok == self.ecfg.eos_id
        # prompt + generated must fit the cache
        out_of_room = self._host_len[slot] >= self.ecfg.max_seq_len - 1
        if (len(req.generated) >= req.max_new_tokens or hit_eos
                or out_of_room):
            # remaining window tokens for this slot are noise (the device
            # kept going); retire discards them by flipping active off
            self._retire(slot)

    def _slot_live(self, win: _Window, slot: int) -> bool:
        """A window's tokens belong to a slot only if the request that
        occupied it AT DISPATCH is still there — identity, not just
        activity: with a window in flight a slot can retire AND be
        re-admitted before its tokens are processed, and the old window's
        tokens must never leak into the new request's stream."""
        return (bool(win.mask[slot]) and bool(self.active[slot])
                and self.slot_req[slot] is win.reqs[slot])

    def _process_window_host(self, win: _Window, window,
                             second=None) -> None:
        """Host-side consumption of one window's tokens. Decode windows
        carry [k, B] (every step, every slot; ``second`` a looped
        decoder's exit passes and pass counts, [k, B, 2], or a layer
        pattern's chosen experts, [k, B, layers, top_k]); verify windows
        carry the model outputs [B, 1+s] and in ``second`` the per-slot
        accepted-draft counts — tokens-per-slot-per-window is VARIABLE
        (1..1+s)."""
        with phase("engine.window.fanout", self.host_phases) as ph:
            t_host0 = time.monotonic()
            win.delivered = {}
            if win.kind == "verify":
                self._process_verify_host(win, window, second)
            else:
                self._process_decode_host(win, window, second)
            self._obs_window(win, t_host0)
            ph.set(tokens=sum(win.delivered.values()),
                   lanes=len(win.delivered), k=win.k, clean=int(win.clean),
                   period_us=round(win.period_s * 1e6),
                   admit_us=round(win.admit_s * 1e6))

    def _process_decode_host(self, win: _Window, window,
                             second=None) -> None:
        shadow: dict[int, list[int]] = {}
        if self._spec_lens:
            # shadow drafts: what WOULD prompt lookup have proposed for
            # this window? Proposed HERE — at processing time, before any
            # of the window's tokens are appended — the proposer history
            # is exactly the pre-window state, so the drafts align with
            # the tokens they are graded against (proposing at DISPATCH
            # would be one in-flight window stale under the steady-state
            # overlap and misalign by k mod cycle-period). The window's
            # real tokens grade them below: a free, always-fresh
            # acceptance estimate that opens the verify gate the moment a
            # stream turns repetitive, with no blind probe windows.
            m = min(win.k, self._spec_lens[-1])
            for slot in range(self.ecfg.max_batch):
                st = self._spec_slots[slot]
                if st is not None and self._slot_live(win, slot):
                    shadow[slot] = st.proposer.propose(m)
        delivered: list[list[int]] = [[] for _ in range(self.ecfg.max_batch)]
        given = list(map(int, win.steps))
        for step in range(win.k):
            for slot in range(self.ecfg.max_batch):
                # (a lane's tokens past the steps it was given are a parked
                # lane's noise; one that ran to its budget retires at its
                # last token anyway)
                if step >= given[slot] or not self._slot_live(win, slot):
                    continue
                if self.slot_req[slot].cancelled:
                    # client gone mid-stream: stop decoding into a queue
                    # nobody reads and free the slot for live work
                    self._retire(slot)
                    continue
                if self._req_expired(self.slot_req[slot]):
                    # deadline passed mid-generation: retire NOW — the
                    # slot's KV blocks return to the pool this window,
                    # not after the remaining budget decodes into a
                    # response nobody is waiting for
                    self._stats["deadline_expired"] += 1
                    self.slot_req[slot].error = \
                        f"{DEADLINE_ERROR}: budget exhausted mid-decode"
                    self._retire(slot)
                    continue
                tok = int(window[step, slot])
                delivered[slot].append(tok)
                self._deliver_token(slot, tok)
        for slot, sh in shadow.items():
            m = min(len(sh), len(delivered[slot]))
            if m == 0:
                continue
            acc = 0
            while acc < m and sh[acc] == delivered[slot][acc]:
                acc += 1
            st = self._spec_slots[slot]
            if st is not None:
                st.observe(m, acc)
        win.delivered = {slot: len(toks)
                         for slot, toks in enumerate(delivered) if toks}
        if win.picks is not None:
            self._note_routed(win, second)
        elif second is not None:
            # a looped decoder: beside each delivered token (a slot's are
            # the window's first steps) the device says which pass the head
            # read, ``exits[step, slot, 0]``, and how many passes its loop
            # ran for it, ``exits[step, slot, 1]``
            exits = second
            for slot, n in win.delivered.items():
                self._stats["loop_tokens"] += n
                self._stats["loop_passes"] += int(exits[:n, slot, 1].sum())
                for step in exits[:n, slot, 0]:
                    self._loop_exit_hist[int(step)] += 1

    def _note_routed(self, win: _Window, picks) -> None:
        """An expert decoder's decode window: ``picks`` [k, B, expert layers,
        top_k], the experts (global ids) the token each lane fed in chose
        at each step. A layer pattern's request keeps those of the tokens it
        was delivered (step j fed in the token before the j-th delivered);
        the counters are over the lanes live at dispatch, in the steps each
        ran: a parked lane's row enters no expert's list, so it counts as
        no pick."""
        if self._keeps_routing:
            for slot, n in win.delivered.items():
                win.reqs[slot].routed.append(picks[:n, slot].copy())
        live = picks[:, win.mask]
        k, n, layers, _ = live.shape
        e = self.cfg.n_experts
        local = live - self.cfg.moe_held_first
        ran = np.arange(k)[:, None] < win.steps[win.mask][None, :]
        held = (local >= 0) & (local < e) & ran[:, :, None, None]
        # one cell a (step, layer, held expert): how many picks it took
        cell = (np.arange(k)[:, None, None, None] * layers
                + np.arange(layers)[None, None, :, None]) * e + local
        hits = np.bincount(cell[held], minlength=k * layers * e)
        st = self._stats
        st["moe_local_picks"] += int(held.sum())
        st["moe_held_touched"] += int((hits > 0).sum())
        st["moe_step_layers"] += k * layers
        st["moe_token_layers"] += layers * int(ran.sum())
        self._held_pick_hist += hits.reshape(k * layers, e).sum(0)

    def _process_verify_host(self, win: _Window, out, n_acc) -> None:
        s = win.spec_len
        win_proposed = win_accepted = 0
        for slot in range(self.ecfg.max_batch):
            if not self._slot_live(win, slot):
                continue
            acc = int(n_acc[slot])
            st = self._spec_slots[slot]
            n_real = int(win.n_real[slot])
            if n_real > 0:
                win_proposed += n_real
                win_accepted += min(acc, n_real)
            if st is not None and n_real > 0:
                # EWMA and counters see only what this slot actually
                # proposed — zero-padded lanes (and any padded TAIL of a
                # partial proposal) must not drag acceptance down for
                # drafts that were never offered. Padding accepted by
                # chance is capped off the accounting too; its tokens are
                # still delivered (they are the model's own outputs).
                st.observe(n_real, min(acc, n_real))
                self._stats["spec_proposed"] += n_real
                self._stats["spec_accepted"] += min(acc, n_real)
            if self.slot_req[slot].cancelled:
                self._retire(slot)
                continue
            if self._req_expired(self.slot_req[slot]):
                self._stats["deadline_expired"] += 1
                self.slot_req[slot].error = \
                    f"{DEADLINE_ERROR}: budget exhausted mid-decode"
                self._retire(slot)
                continue
            req = self.slot_req[slot]
            n_delivered = 0
            for i in range(acc + 1):
                self._deliver_token(slot, int(out[slot, i]))
                n_delivered += 1
                if self.slot_req[slot] is not req:
                    break          # EOS / budget / room hit inside the run
            if n_delivered:
                win.delivered[slot] = n_delivered
        win.spec_stats = (win_proposed, win_accepted)
