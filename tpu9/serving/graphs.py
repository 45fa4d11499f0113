"""Serving graph factory: every jitted/AOT-compiled XLA computation the
engine dispatches, in one module (ISSUE 9 engine split).

The engine split's graph-building third: prefill (bucketed dense +
chunked paged + fused admission groups), windowed decode, speculative
verify, and the pool splice/gather plumbing. The factory owns the
compiled-executable cache and is the ONLY place serving code traces jax —
the engine orchestrates admission/scheduling/fan-out around these
callables and never opens a ``jax.jit`` itself.

Sharding boundary: the factory is handed a :mod:`tpu9.serving.shard`
policy and pins every KV-state output with ``policy.constrain_kv`` before
returning it from a traced body — on a mesh that keeps the donated pool
head-sharded across every round trip; on the single-device policy the
hook is the identity, so a ``1x1`` engine traces exactly the graphs the
pre-split engine did (same cache keys, no constraint ops).

Dtype boundary: int8 KV quantize/dequant stays in ``ops.quant`` +
``models.transformer``; the factory only routes the scale planes through
the same physical indices as the payload (``traced_splice``).
"""

from __future__ import annotations

import logging
import re
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp

from ..models import kvstate
from ..models.hybrid import HYBRID_SCOPES, MLA_QUERY_SCOPES
from ..models.shortconv import CONV_SCOPES
from ..models.ssm import SSM_SCOPES
from ..models.transformer import (DEVICE_SCOPES, LATENT_MOE_SCOPES,
                                  LOOP_SCOPES, SUMMARY_SCOPES,
                                  decoder_forward)
from ..ops.sampling import sample_logits

Params = dict[str, Any]

log = logging.getLogger("tpu9.serving")


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# an operand: a %name not behind "=" (calls=%body, to_apply=%add, ...)
_HLO_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
# instructions that never run as an operation of their own on the device
# (a ``-start`` is on a trace's line of asynchronous operations, its
# ``-done`` among the operations)
_HLO_NO_OP = re.compile(
    r" (parameter|get-tuple-element|tuple|constant|bitcast|[\w\-]+-start)\(")


def hlo_scopes(text: str, scopes=DEVICE_SCOPES) -> dict:
    """``{scope: [instruction names]}`` of one compiled program's HLO text:
    each instruction that runs on its own (the body of a fusion does not)
    under the innermost ``jax.named_scope`` of ``scopes`` on its
    ``op_name`` path. An instruction the compiler made carries no
    ``op_name``: a fusion takes its body's root's, anything else (the
    ``copy-done`` of an asynchronous copy, the ``slice-done`` of a weight
    prefetch) its first operand's, else that of the first instruction it
    feeds. A profiler trace names device operations by these names."""
    known = set(scopes)

    def innermost(path: str) -> str:
        return next((part for part in reversed(path.split("/"))
                     if part in known), "")

    rows: list = []             # (computation, name, runs on its own)
    scope_of: dict = {}         # instruction -> its scope, own or taken
    first_user: dict = {}       # instruction -> the first one it feeds
    made: set = set()           # instructions with no op_name at all
    body_scope: dict = {}       # computation -> scope of its root
    computation = ""
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            computation = m.group(1) if m else ""
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        operands = _HLO_OPERAND.findall(line, m.end())
        for operand in operands:
            first_user.setdefault(operand, name)
        found = _HLO_OP_NAME.search(line)
        body = _HLO_FUSED.search(line) if " fusion(" in line else None
        if found:               # the program's own: a scope, or none
            scope = innermost(found.group(1))
            if scope:
                body_scope[computation] = scope
        else:                   # the compiler's: taken from a neighbour
            made.add(name)
            scope = (body_scope.get(body.group(1), "") if body else "") \
                or (scope_of.get(operands[0], "") if operands else "")
        scope_of[name] = scope
        rows.append((computation, name, not _HLO_NO_OP.search(line),
                     body.group(1) if body else ""))
    fused = {body for _, _, _, body in rows if body}
    out: dict = {}
    for computation, name, alone, _ in rows:
        if computation in fused or not alone:
            continue
        scope, user = scope_of[name], name
        for _ in range(4):      # copy-start -> copy-done -> what it feeds
            if scope or name not in made or user not in first_user:
                break
            user = first_user[user]
            scope = scope_of.get(user, "")
        if scope:
            out.setdefault(scope, []).append(name)
    return out


class GraphFactory:
    """Builds + caches the engine's compiled graphs for one (model,
    engine-config, sharding-policy) triple. ``chunk`` is the validated
    chunked-prefill length (0 = dense mode); ``kv_quant`` whether the
    paged pool carries int8 payload + scale planes."""

    def __init__(self, cfg, ecfg, policy, chunk: int = 0,
                 kv_quant: bool = False):
        self.cfg = cfg
        self.ecfg = ecfg
        self.policy = policy
        self.chunk = chunk
        self.kv_quant = kv_quant
        # chunks per admission-group dispatch; 1 (no group graph) in dense
        # mode and where one group would not fit the scratch
        g = max(1, ecfg.admit_group_chunks)
        self.group_chunks = g if chunk and g * chunk <= ecfg.max_seq_len \
            else 1
        # rows of the batch-1 prefill scratch (``max_seq_len`` for plain
        # attention), and the blocks a splice writes before its chunk's own:
        # with ``attn_window`` the page of summaries that the chunk's
        # program may have made, which lies just below the chunk's entries
        from .paged_kv import scratch_len
        self.scratch_len = scratch_len(cfg, ecfg.max_seq_len, chunk)
        self.splice_lead = 1 if cfg.attn_window else 0
        self.compiled: dict[Any, Any] = {}
        # recompile sentinel (ISSUE 11): executable-cache misses. After
        # seal() (warmup/precompile done) a miss means steady-state
        # serving is about to stall every active stream behind an XLA
        # compile — the runtime face of graphcheck's closed-signature
        # pass, surfaced via engine.stats()["graph_compiles*"].
        self.compiles = 0
        self.post_seal_compiles = 0
        # cumulative seconds serving stalled behind post-seal compiles
        # (ISSUE 12: the goodput accountant's "recompile_stall" waste
        # bucket) — measured as the first dispatch's wall time, since
        # jax.jit compiles lazily at that first call
        self.post_seal_stall_s = 0.0
        self._sealed = False
        # graph name -> ``tpu_custom_call`` count in its AOT-compiled HLO
        # (:meth:`precompile`): the evidence that a step really contains
        # the pallas kernels rather than having taken the XLA oracle
        self.kernel_calls: dict[str, int] = {}
        # graph name -> {scope: [HLO instruction names]} of its
        # AOT-compiled text (:func:`hlo_scopes`); a graph whose executable
        # names no scope (the persistent cache served one compiled before
        # the scopes existed: its key ignores them) is left out
        self.device_scopes: dict[str, dict] = {}

    def _build(self, key, builder):
        """Cache-or-build a graph under ``key`` — the ONE miss path, so
        the sentinel can't be bypassed by a new getter."""
        fn = self.compiled.get(key)
        if fn is None:
            self.compiles += 1
            if self._sealed:
                self.post_seal_compiles += 1
                log.warning(
                    "post-warmup graph compile: key=%r — a steady-state "
                    "window is stalling behind an XLA compile; the "
                    "precompile signature set is open (graphcheck GRA005 "
                    "should have caught this)", key)
                fn = self.compiled[key] = self._timed_first_call(
                    key, builder())
                return fn
            fn = self.compiled[key] = builder()
        return fn

    def _timed_first_call(self, key, real):
        """Wrap a post-seal-built callable so its FIRST dispatch — the one
        that pays the XLA compile — is timed into ``post_seal_stall_s``,
        then unwrap (steady state dispatches the bare executable)."""
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            out = real(*args, **kwargs)
            self.post_seal_stall_s += time.perf_counter() - t0
            self.compiled[key] = real
            return out
        return timed

    def seal(self) -> None:
        """Mark the executable cache complete: every signature the serve
        loop can request is compiled. Called by engine warmup/precompile;
        later misses are counted + logged as recompile incidents."""
        self._sealed = True

    # -- decode window -------------------------------------------------------

    def build_decode(self, k: int = 1):
        """The decode window of ``k`` steps. ``steps_left`` int32 [B] says how
        many of them each lane may run (0: an idle lane): lane ``b`` is live
        in step ``j`` iff ``steps_left[b] > j``, and a lane past its number
        is PARKED — an idle lane from that step on, whatever the window's
        size: its length stands, no state of its own advances, no expert
        enters the held list for it, and its tokens are noise the host never
        reads. The host's budget and cache room end HERE (``WindowScheduler.
        lane_steps``), so no lane's ending shrinks the others' window."""
        cfg, ecfg, policy = self.cfg, self.ecfg, self.policy

        def one_step(params, kv_cache, last_token, cache_len, active, rng,
                     vectors):
            positions = cache_len[:, None]          # next position per slot
            # an idle lane attends to nothing: its length is 0, so the paged
            # kernel walks no page for it (its token, like its write to the
            # trash block, is discarded). A parked lane's table is real: its
            # one row a step lands at its own next position, past its last
            # token, which every kernel masks by length and no page another
            # table shares holds (a shared page is full of a prompt's rows)
            live = active.astype(jnp.int32)
            table = None
            if cfg.attn_window:
                # a lane whose next token opens a window: the window it
                # closed becomes its summaries first
                kv_cache = self.traced_summarise_pool(vectors, kv_cache,
                                                      cache_len, active)
                # ... which a parked lane's did not, so the entry of its next
                # position lies INSIDE the closed window's rows: its row goes
                # through a table of trash blocks (block 0) instead
                table = kv_cache["table"]
                kv_cache = dict(kv_cache, table=table * live[:, None])
            # a looped decoder also says which pass the head read and how
            # many passes ran: ``exits`` is ``(exit_info [B, 1, 2],)`` for it
            # and empty for a plain one
            if not cfg.uniform or (cfg.n_experts and not cfg.looped):
                # the step says which lanes are live: an idle lane advances
                # no KDA state of a layer pattern and puts no expert on the
                # list of those an expert layer reads; the step then also
                # says which experts every lane's token chose in every
                # expert layer, ``(picks [B, 1, layers, top_k],)`` — a layer
                # pattern always, a plain expert decoder where its layers
                # read by that list (``moe.takes_held_form``)
                logits, kv_cache, *exits = decoder_forward(
                    params, last_token, cfg, positions=positions,
                    kv_cache=kv_cache, cache_len=(cache_len + 1) * live,
                    decode=True, mesh=policy.mesh, n_valid=live,
                    return_moe_picks=True)
            else:
                logits, kv_cache, *exits = decoder_forward(
                    params, last_token, cfg, positions=positions,
                    kv_cache=kv_cache, cache_len=(cache_len + 1) * live,
                    decode=True, mesh=policy.mesh, return_exit=cfg.looped)
            if table is not None:
                kv_cache = dict(kv_cache, table=table)
            rng, sub = jax.random.split(rng)
            next_tok = sample_logits(logits[:, -1], sub,
                                     temperature=ecfg.temperature,
                                     top_k=ecfg.top_k, top_p=ecfg.top_p)
            # only live slots advance; idle lanes stay parked at 0 so the
            # token-pressure signal reflects real cache occupancy
            new_len = cache_len + live
            return (next_tok[:, None].astype(jnp.int32), kv_cache, new_len,
                    rng, exits)

        def decode(params, kv_cache, last_token, cache_len, steps_left, rng):
            # the layers' summary vectors, stacked once a program
            vectors = self._summary_vectors(params) if cfg.attn_window \
                else None

            def body(carry, step):
                last, kv, clen, r = carry
                last, kv, clen, r, exits = one_step(
                    params, kv, last, clen, steps_left > step, r, vectors)
                return (last, kv, clen, r), \
                    (last[:, 0], *(e[:, 0] for e in exits))

            (last, kv_cache, cache_len, rng), per_step = jax.lax.scan(
                body, (last_token, kv_cache, cache_len, rng),
                jnp.arange(k, dtype=jnp.int32))
            # toks [k, B] (and a looped decoder's exit pass and pass count,
            # [k, B, 2], or an expert decoder's chosen experts, [k, B, expert
            # layers, top_k]): the host consumes the whole window in one sync
            return (last, policy.constrain_kv(kv_cache), cache_len, rng,
                    *per_step)

        return jax.jit(decode, donate_argnums=(1,))

    def decode_k(self, k: int):
        return self._build(("decode", k), lambda: self.build_decode(k))

    # -- speculative verify --------------------------------------------------

    def build_verify(self, s: int):
        """Jitted speculative-verify graph (ISSUE 5 tentpole): ONE batched
        forward over ``[B, 1+s]`` positions — column 0 is the device
        last_token, columns 1..s the host-proposed draft tokens. The model
        emits its OWN token at every position; a draft survives only while
        it equals the model's output, so the emitted stream is exactly
        what classic decode would have produced (greedy parity is
        bit-exact — drafts can only be cheap, never wrong). Per slot the
        graph returns the accepted-prefix length and the model's bonus
        token, and advances cache_len past accepted positions only —
        rejected draft positions keep garbage KV that attention masks out
        and the next window overwrites (paged re-splice / dense
        re-scatter)."""
        cfg, ecfg, policy = self.cfg, self.ecfg, self.policy
        t = s + 1

        def verify(params, kv_cache, last_token, drafts, cache_len,
                   active, rng):
            tokens = jnp.concatenate(
                [last_token, drafts.astype(jnp.int32)], axis=1)  # [B, t]
            positions = cache_len[:, None] + jnp.arange(t)[None, :]
            logits, kv_cache = decoder_forward(
                params, tokens, cfg, positions=positions,
                kv_cache=kv_cache, cache_len=cache_len + t, decode=False,
                mesh=policy.mesh)
            rng, sub = jax.random.split(rng)
            out = sample_logits(logits, sub, temperature=ecfg.temperature,
                                top_k=ecfg.top_k,
                                top_p=ecfg.top_p).astype(jnp.int32)  # [B, t]
            # longest agreeing prefix of the drafts, per slot
            agree = (tokens[:, 1:] == out[:, :-1]).astype(jnp.int32)
            n_acc = jnp.cumprod(agree, axis=1).sum(axis=1)        # [B]
            # the model's own next token after the accepted run
            bonus = jnp.take_along_axis(out, n_acc[:, None], axis=1)
            new_len = cache_len + (n_acc + 1) * active.astype(jnp.int32)
            return (bonus, policy.constrain_kv(kv_cache), new_len, rng,
                    out, n_acc)

        return jax.jit(verify, donate_argnums=(1,))

    def verify_fn(self, s: int):
        return self._build(("verify", s), lambda: self.build_verify(s))

    # -- dense prefill -------------------------------------------------------

    def prefill_fn(self, bucket: int):
        cfg, policy = self.cfg, self.policy

        def build():
            def prefill(params, tokens, length):
                # tokens [1, bucket] padded; returns logits at the last
                # real token and the per-layer k/v for the prefix.
                logits, cache = decoder_forward(
                    params, tokens, cfg,
                    kv_cache=kvstate.init_kv_cache(cfg, 1, bucket),
                    decode=False,
                    mesh=policy.mesh)
                last = logits[0, length - 1]
                return last, policy.constrain_kv(cache)

            return jax.jit(prefill)

        return self._build(bucket, build)

    def dense_splice_fn(self, bucket: int):
        """Jitted, cache-donating copy of a prefill's [L,1,bucket,...] KV
        into one slot's lanes of the dense [L,B,S,...] cache."""
        policy = self.policy

        def build():
            @jax.named_scope("kv.splice")
            def splice(k, v, ck, cv, slot):
                k = jax.lax.dynamic_update_slice(
                    k, ck[:, :, :bucket], (0, slot, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    v, cv[:, :, :bucket], (0, slot, 0, 0, 0))
                out = policy.constrain_kv({"k": k, "v": v})
                return out["k"], out["v"]

            return jax.jit(splice, donate_argnums=(0, 1))

        return self._build(("dsplice", bucket), build)

    # -- paged chunked prefill -----------------------------------------------

    def traced_chunk_step(self, params, scratch, tok_row, offset,
                          last_idx):
        """Traced body shared by the single-chunk and admission-group
        graphs (one implementation — the two admission paths must never
        diverge): prefill the contiguous tokens of ``tok_row`` (one chunk,
        or a group's g·C) into the scratch at ``offset`` and return the
        logits at ``last_idx`` — and, last, for a layer pattern the experts
        every token of the row chose, ``[width, expert layers, top_k]``."""
        width = tok_row.shape[0]
        positions = offset + jnp.arange(width)[None, :]
        if self.cfg.attn_window:
            scratch = self.traced_summarise_scratch(params, scratch, offset)
        if not self.cfg.uniform:
            # the scratch carries the admitted sequence's KDA state from
            # chunk to chunk: zero where the sequence starts, advanced by the
            # chunk's real tokens alone (its tail is padding)
            scratch = dict(scratch, **{
                name: jnp.where(offset == 0, jnp.zeros_like(scratch[name]),
                                scratch[name])
                for name in kvstate.lane_shapes(self.cfg, 1)})
            # (a listed pattern without experts says no picks)
            logits, scratch, *picks = decoder_forward(
                params, tok_row[None, :], self.cfg, positions=positions,
                kv_cache=scratch, cache_len=offset + width, decode=False,
                n_valid=jnp.reshape(last_idx + 1, (1,)),
                return_moe_picks=True)
            extras = tuple(p[0] for p in picks)
        else:
            extras = ()
            logits, scratch = decoder_forward(
                params, tok_row[None, :], self.cfg, positions=positions,
                kv_cache=scratch, cache_len=offset + width, decode=False,
                mesh=self.policy.mesh)
        last = jax.lax.dynamic_index_in_dim(
            logits[0], last_idx, axis=0, keepdims=False)
        return (last, scratch) + extras

    def traced_splice(self, pool, scratch_k, scratch_v, offset, phys,
                      scratch_tails=None):
        """Traced block copy shared by the splice and admission-group
        graphs: scratch positions [offset, offset + len(phys)·BS) → pool
        blocks phys[0..] (one chunk's C/BS blocks, or a group's g·C/BS).
        An int8 pool quantizes each block on the way in (per-vector absmax
        scales land in the scale planes at the same physical index).
        ``scratch_tails``: the state the scratch keeps a BLOCK (a listed
        pattern's short convolutions: ``kvstate.BLOCK_TAIL``), which goes
        into the pool beside the pages' rows; None for every other
        decoder."""
        bs = self.ecfg.kv_block_size
        if scratch_tails is not None:
            with jax.named_scope("kv.splice"):
                pool = kvstate.splice_block_tails(pool, scratch_tails,
                                                  offset // bs, phys)
        # the scratch is addressed by entry. With ``attn_window`` the first
        # block of ``phys`` takes the page BEFORE the chunk's own: the
        # summaries of the window that this chunk's program closed (the
        # host names the trash block there for every other chunk)
        offset = self.cfg.kv_entry(offset)
        if self.splice_lead:
            offset = offset - bs            # below 0 for the first chunk

        def source(j):
            at = offset + j * bs
            return jnp.maximum(at, 0) if self.splice_lead and j == 0 else at

        with jax.named_scope("kv.splice"):
            for j in range(phys.shape[0]):
                blk_k = jax.lax.dynamic_slice_in_dim(
                    scratch_k[:, 0], source(j), bs, axis=1)
                blk_v = jax.lax.dynamic_slice_in_dim(
                    scratch_v[:, 0], source(j), bs, axis=1)
                # [L,bs,KH,D], written as the pool stores them
                pool = kvstate.splice_block(pool, phys, j, blk_k, blk_v)
            return self.policy.constrain_kv(pool)

    # -- the summarise of a closed window (``attn_window``) -------------------

    def _summary_vectors(self, params) -> tuple:
        """``(mu, phi)``, each ``[L, KH, D]``: the layers' vectors stacked,
        for a loop that takes its layer as an operand."""
        return tuple(jnp.stack([layer[name] for layer in params["layers"]])
                     for name in ("summary_mu", "summary_phi"))

    def traced_summarise_pool(self, vectors, kv_cache, cache_len, active):
        """Inside a decode step, before the forward pass (``vectors``: the
        layers' summary vectors stacked, :meth:`_summary_vectors`): for every live
        lane whose next position is the first of a window, the pages of the
        window it closed are read (the ``window / block`` columns of its
        table row from the window's first on), summarised layer by layer,
        and the summaries written as ONE page, over the window's own first.
        A device loop of ``lanes that roll x layers`` trips: a step in which
        no lane rolls over runs none and pays for none. The ``kv.summarise``
        scope holds the loop's body and nothing else, so that a trace's
        operations under it are one trip each."""
        from ..ops.summary_attention import summarise
        cfg = self.cfg
        w, layers = cfg.attn_window, cfg.n_layers
        pages = w // self.ecfg.kv_block_size
        shape = (w, cfg.n_kv_heads, cfg.head_dim)
        table = kv_cache["table"]
        mu, phi = vectors
        rolls = (active & (cache_len > 0)
                 & (cache_len % w == 0)).astype(jnp.int32)

        @jax.named_scope("kv.summarise")
        def one(i, pools):
            # trip i: layer i % L of the (i // L)-th lane that rolls over
            lane = jnp.argmax(jnp.cumsum(rolls) > i // layers)
            layer = i % layers
            first = cache_len[lane] // w - 1    # column of the closed window
            cols = jax.lax.dynamic_slice_in_dim(table[lane], first, pages)
            summaries = summarise(
                *(pool[layer, cols].reshape(shape) for pool in pools),
                mu[layer], phi[layer], cfg.attn_chunk)
            return tuple(jax.lax.dynamic_update_slice(
                pool, s.astype(pool.dtype)[None, None],
                (layer, cols[0], 0, 0, 0))
                for pool, s in zip(pools, summaries))

        k, v = jax.lax.fori_loop(
            0, jnp.sum(rolls) * layers, one,
            (kv_cache["k"], kv_cache["v"]))
        return dict(kv_cache, k=k, v=v)

    def traced_summarise_scratch(self, params, scratch, offset):
        """At the head of a chunk (or group) program, before the forward
        pass: where the chunk's first position opens a window, the window
        before it is summarised in the scratch, layer by layer, its
        summaries written over its own first entries — just below where the
        chunk's tokens go. ``prefill_chunk`` (and a group) divides the
        window, so no chunk straddles one. A chunk that opens no window runs
        no trip of the loop."""
        from ..ops.summary_attention import summarise
        cfg = self.cfg
        w = cfg.attn_window
        size = (1, 1, w, cfg.n_kv_heads, cfg.head_dim)
        mu, phi = self._summary_vectors(params)
        opens = (offset > 0) & (offset % w == 0)
        first = cfg.kv_entry(offset) - cfg.window_entries

        @jax.named_scope("kv.summarise")
        def one(layer, caches):
            at = (layer, 0, first, 0, 0)
            summaries = summarise(
                *(jax.lax.dynamic_slice(c, at, size)[0, 0] for c in caches),
                mu[layer], phi[layer], cfg.attn_chunk)
            return tuple(jax.lax.dynamic_update_slice(
                c, s.astype(c.dtype)[None, None], at)
                for c, s in zip(caches, summaries))

        k, v = jax.lax.fori_loop(
            0, jnp.where(opens, cfg.n_layers, 0), one,
            (scratch["k"], scratch["v"]))
        return dict(scratch, k=k, v=v)

    def lane_splice_fn(self):
        """Jitted, lane-donating copy of the scratch's KDA state (one lane:
        the sequence just admitted) into lane ``slot`` of the engine's: the
        last program of a layer pattern's admission."""
        def build():
            @jax.named_scope("kv.splice")
            def lane_splice(lanes, scratch, slot):
                return {name: jax.lax.dynamic_update_slice_in_dim(
                    lanes[name], scratch[name], slot, axis=1)
                    for name in lanes}

            return jax.jit(lane_splice, donate_argnums=(0,))

        return self._build("lanesplice", build)

    def tail_restore_fn(self):
        """Jitted read of one page's tails (``kvstate.BLOCK_TAIL``) as one
        lane of state: the scratch's, for a sequence admitted behind that
        page. The program of a prefix hit beside short convolutions, run
        once an admission after the gather."""
        def build():
            @jax.named_scope("kv.gather")
            def tail_restore(tails, block):
                (name,) = kvstate.LANE_KINDS["conv"]
                return {name: kvstate.block_tail_read(tails, block)}

            return jax.jit(tail_restore)

        return self._build("tailrestore", build)

    def restore_tails(self, kv_cache, block: int) -> dict:
        """``{name: one lane of state}``: the tails as of the last row of
        physical block ``block``, for the scratch to start a suffix from."""
        return self.tail_restore_fn()(kv_cache[kvstate.BLOCK_TAIL], block)

    @property
    def restores_tails(self) -> bool:
        """Whether a prefix hit has state to restore: the prefix cache
        beside layers whose state the pool keeps a block."""
        return self.ecfg.prefix_cache_blocks > 0 \
            and bool(kvstate.block_tail_shapes(self.cfg, 1))

    def chunk_fn(self):
        """Jitted chunked-prefill step: write one C-token chunk into the
        batch-1 dense scratch at ``offset``, attend over prefix+chunk, and
        return the logits at ``last_idx`` (the chunk's final real token).
        Shapes are (C, S) — prompt length never changes the graph."""
        policy = self.policy

        def build():
            def chunk(params, tokens, offset, scratch, last_idx):
                last, scratch, *picks = self.traced_chunk_step(
                    params, scratch, tokens[0], offset, last_idx)
                return (last, policy.constrain_kv(scratch), *picks)

            return jax.jit(chunk, donate_argnums=(3,))

        return self._build(("chunk", self.chunk), build)

    def gather_fn(self):
        """Jitted densify of ONE slot's table row into the scratch (prefix
        reuse: cached blocks → scratch so chunk prefill can attend them).
        An int8 pool dequantizes here — the scratch is always the model
        dtype, so chunk prefill attends exact dequantized values. The
        traced body derives the table width from the row argument (one
        cache entry regardless of width — it never changes mid-lifetime)."""
        s = self.scratch_len
        dt = self.cfg.dtype
        policy = self.policy
        # a prefix cache over latent pages (a pattern with no state a lane:
        # with state the cache is refused and this program is never run)
        flat = self.cfg.latent_rows and not self.cfg.lane_state

        def build():
            @jax.named_scope("kv.gather")
            def gather(pool, row):
                # pool [L, N, BS, KH, D], row [MB] → dense [L, 1, S, KH,
                # D]. The row's final column is the ALWAYS-TRASH block —
                # slice it off so the densified prefix has the exact
                # scratch shape (an S+BS-wide scratch trips the forward's
                # refusal of a cache longer than the model's positions
                # when max_seq_len == that limit)
                def one(name):
                    g = kvstate.read_blocks(pool, name, row, flat)
                    l, mb_, bs, kh, d = g.shape      # [L, MB, BS, KH, D]
                    return g.astype(dt).reshape(
                        l, 1, mb_ * bs, kh, d)[:, :, :s]
                return policy.constrain_kv({"k": one("k"), "v": one("v")})

            return jax.jit(gather)

        return self._build("gather", build)

    def splice_shape(self, g: int) -> tuple:
        """Shape of the physical-block operand of a splice of ``g`` chunks:
        ``[C/BS]`` for one chunk, ``[g, C/BS]`` for a group; flat and one
        longer (the leading page of summaries) with ``attn_window``."""
        nb = self.chunk // self.ecfg.kv_block_size
        if self.splice_lead:
            return (g * nb + self.splice_lead,)
        return (nb,) if g == 1 else (g, nb)

    @staticmethod
    def scratch_tails(scratch) -> tuple:
        """The last operand of the splice program: ``(the scratch's state a
        block,)`` where it keeps any, else nothing."""
        return (scratch[kvstate.BLOCK_TAIL],) \
            if kvstate.BLOCK_TAIL in scratch else ()

    def splice_fn(self):
        """Jitted copy of one chunk's blocks from the scratch into their
        physical pool blocks. C/BS is static → one graph."""
        return self._build("splice", lambda: jax.jit(
            self.traced_splice, donate_argnums=(0,)))

    def chunk_group_fn(self, g: int):
        """Admission-group graph: ONE forward over the ``g·C`` contiguous
        positions of ``g`` chunks, then the splice of their g·C/BS blocks
        into the pool. The weights cross the HBM bus once per group, not
        once per chunk, and one dispatch replaces 2g. Only the group's
        final chunk may be partial: ``last_idx`` is its last real token's
        index in that chunk, and the logits returned are that token's, so
        the caller can sample the first output."""
        policy = self.policy
        c = self.chunk

        def build():
            def group(params, pool, scratch, toks, offset, last_idx, phys):
                # toks [g, C] phys [g, C/BS]; offset, last_idx scalars
                last, scratch, *picks = self.traced_chunk_step(
                    params, scratch, toks.reshape(g * c), offset,
                    (g - 1) * c + last_idx)
                pool = self.traced_splice(
                    pool, scratch["k"], scratch["v"], offset,
                    phys.reshape(-1), scratch.get(kvstate.BLOCK_TAIL))
                return (pool, policy.constrain_kv(scratch), last, *picks)

            return jax.jit(group, donate_argnums=(1, 2))

        return self._build(("chunkgroup", g), build)

    # -- compile-ahead (AOT) + static verification hooks ---------------------

    def lowering_jobs(self, params, kv_cache: Params, pool: Params,
                      scratch: Params, mb: int, buckets, spec_lens,
                      rng) -> Iterator[tuple]:
        """Enumerate every steady-state serving graph as ``(key, fn,
        abstract_args)`` — THE introspection surface (ISSUE 11): both
        :meth:`precompile` (lower+compile each job) and graphcheck's
        Pass A (lower each job and verify sharding/dtype/donation
        invariants from the jaxpr and compiled artifact) drive this one
        enumeration, so the verified signature set and the precompiled
        signature set cannot drift apart. Arguments may be real arrays or
        ``jax.ShapeDtypeStruct`` trees — only shapes/dtypes are read.
        Scalar positions yield concrete ints — the weak-typed aval the
        serve loop's python-int arguments produce."""
        policy = self.policy
        pspec = policy.abstract(params)
        b = self.ecfg.max_batch
        i32 = jnp.int32
        if self.chunk:
            c = self.chunk
            ascratch = policy.abstract(scratch, kv=True)
            apool = policy.abstract(pool, kv=True)
            yield (("chunk", c), self.chunk_fn(),
                   (pspec, jax.ShapeDtypeStruct((1, c), i32), 0, ascratch,
                    0))
            yield ("splice", self.splice_fn(),
                   (apool, ascratch["k"], ascratch["v"], 0,
                    jax.ShapeDtypeStruct(self.splice_shape(1), i32))
                   + self.scratch_tails(ascratch))
            if self.restores_tails:
                yield ("tailrestore", self.tail_restore_fn(),
                       (apool[kvstate.BLOCK_TAIL], 0))
            yield ("gather", self.gather_fn(),
                   (apool, jax.ShapeDtypeStruct((mb,), i32)))
            g = self.group_chunks
            if g > 1:
                yield (("chunkgroup", g), self.chunk_group_fn(g),
                       (pspec, apool, ascratch,
                        jax.ShapeDtypeStruct((g, c), i32), 0, 0,
                        jax.ShapeDtypeStruct(self.splice_shape(g), i32)))
            names = tuple(kvstate.lane_shapes(self.cfg, 1))
            if names:           # a pattern with KDA layers: state a lane
                akv = policy.abstract(kv_cache, kv=True)
                yield ("lanesplice", self.lane_splice_fn(),
                       ({n: akv[n] for n in names},
                        {n: ascratch[n] for n in names}, 0))
        else:
            for bucket in buckets:
                pre = jax.ShapeDtypeStruct(
                    *kvstate.dense_shapes(self.cfg, 1, bucket)["k"])
                adense = policy.abstract(
                    {"k": kv_cache["k"], "v": kv_cache["v"]}, kv=True)
                yield (bucket, self.prefill_fn(bucket),
                       (pspec, jax.ShapeDtypeStruct((1, bucket), i32), 1))
                yield (("dsplice", bucket), self.dense_splice_fn(bucket),
                       (adense["k"], adense["v"], pre, pre, 0))
        kv_spec = policy.abstract(kv_cache, kv=True)
        arng = policy.abstract(rng)
        for k in self.ecfg.decode_steps:
            yield (("decode", k), self.decode_k(k),
                   (pspec, kv_spec, jax.ShapeDtypeStruct((b, 1), i32),
                    jax.ShapeDtypeStruct((b,), i32),
                    jax.ShapeDtypeStruct((b,), i32),
                    arng))
        for s in spec_lens:
            yield (("verify", s), self.verify_fn(s),
                   (pspec, kv_spec, jax.ShapeDtypeStruct((b, 1), i32),
                    jax.ShapeDtypeStruct((b, s), i32),
                    jax.ShapeDtypeStruct((b,), i32),
                    jax.ShapeDtypeStruct((b,), jnp.bool_),
                    arng))

    def reachable_keys(self, buckets, spec_lens) -> set:
        """Every executable-cache key the serve loop can request in steady
        state — the OTHER half of graphcheck's closed-signature invariant
        (GRA005: this set must equal the :meth:`lowering_jobs` key set).

        One entry per dispatch site; when adding a dispatch that resolves
        a new key shape, extend BOTH this enumeration and
        ``lowering_jobs`` or the gate fails:

        - ``("decode", k)``: ``WindowScheduler.pick_steps`` and the
          admission-interleaved window pick only from
          ``ecfg.decode_steps``.
        - ``("verify", s)``: ``WindowScheduler.spec_room_len`` picks only
          from the engine's ``spec_lens`` buckets.
        - ``("chunk", c)`` / ``"splice"`` / ``"gather"``: paged admission
          — ONE validated chunk length; partial tail groups reuse these,
          never a fresh group width.
        - ``("chunkgroup", g)``: paged admission dispatches FULL groups
          only (``_admit_paged`` drops to the single-chunk graphs for
          tails).
        - ``bucket`` / ``("dsplice", bucket)``: dense admission buckets,
          clamped to max_seq_len by the engine (``_bucket_for``).
        """
        keys: set = {("decode", k) for k in self.ecfg.decode_steps}
        keys |= {("verify", s) for s in spec_lens}
        if self.chunk:
            keys |= {("chunk", self.chunk), "splice", "gather"}
            if self.group_chunks > 1:
                keys.add(("chunkgroup", self.group_chunks))
            if kvstate.lane_shapes(self.cfg, 1):
                # the end of every paged admission of a layer pattern that
                # keeps state a lane (one with no KDA layer has no such
                # program)
                keys.add("lanesplice")
            if self.restores_tails:
                keys.add("tailrestore")      # behind a prefix hit's gather
        else:
            for bucket in buckets:
                keys |= {bucket, ("dsplice", bucket)}
        return keys

    def precompile(self, params, kv_cache: Params, pool: Params,
                   scratch: Params, mb: int, buckets, spec_lens,
                   rng) -> dict:
        """AOT-compile every steady-state serving graph from SHAPES alone.

        XLA needs param shapes/dtypes, not values — so serving bring-up
        can run this concurrently with weight streaming (``params`` may be
        a ``jax.ShapeDtypeStruct`` tree) instead of serializing a
        multi-second compile behind the weight load. Each
        ``.lower(...).compile()`` executable replaces the jitted function
        under the same cache key the serve loop resolves. On a mesh
        policy the abstract specs carry NamedShardings, so the lowered
        executables are the exact SPMD programs the serve loop will
        dispatch. Seals the cache afterwards: any later miss is a
        recompile incident (counted + logged loudly)."""
        timings: dict[str, float] = {}
        for key, fn, args in self.lowering_jobs(
                params, kv_cache, pool, scratch, mb, buckets, spec_lens,
                rng):
            if not hasattr(fn, "lower"):
                continue                  # already an AOT executable
            t0 = time.perf_counter()
            self.compiled[key] = fn.lower(*args).compile()
            name = "_".join(str(p) for p in key) \
                if isinstance(key, tuple) else str(key)
            timings[f"compile_{name}_s"] = \
                round(time.perf_counter() - t0, 4)
            text = self.compiled[key].as_text()
            self.kernel_calls[name] = text.count("tpu_custom_call")
            # a plain program runs nothing under the loop's scopes
            scopes = hlo_scopes(
                text, DEVICE_SCOPES + LOOP_SCOPES + SUMMARY_SCOPES
                + HYBRID_SCOPES + MLA_QUERY_SCOPES + SSM_SCOPES
                + LATENT_MOE_SCOPES + CONV_SCOPES)
            if scopes:
                self.device_scopes[name] = scopes
            else:
                log.warning(
                    "graph %s: its executable names none of the model's "
                    "scopes — the compile cache served one built before "
                    "they existed (its key ignores op names); a trace of "
                    "it cannot be read by scope until the entry is "
                    "removed from the cache directory", name)
        self.seal()
        return timings


def abstract_state(cfg, ecfg, policy, kv_quant: bool = False) -> dict:
    """Device-free abstract serving state for :meth:`GraphFactory.
    lowering_jobs`: the kv_cache/pool/scratch ``ShapeDtypeStruct`` trees
    an engine of this (model, engine-config) pair would hold, without
    allocating a byte. Shapes come from the same sources the engine uses
    (``KvPool`` for the paged pool, ``kvstate.init_kv_cache`` via
    ``eval_shape`` for dense/scratch), so graphcheck lowers EXACTLY the
    engine's graphs.
    Returns ``{"kv_cache", "pool", "scratch", "mb", "rng"}`` (paged) or
    the dense equivalents (empty pool/scratch, mb=0)."""
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    if ecfg.kv_block_size:
        from .kvpool import KvPool
        mgr = KvPool(cfg, ecfg, kv_quant, policy)
        kv_cache = mgr.array_specs()
        pool = {name: kv_cache[name] for name in mgr.program_names()}
        from .paged_kv import scratch_len
        chunk = ecfg.prefill_chunk or min(ecfg.prefill_buckets)
        scratch = jax.eval_shape(lambda: kvstate.init_kv_cache(
            cfg, 1, scratch_len(cfg, ecfg.max_seq_len, chunk),
            block=ecfg.kv_block_size))
        return {"kv_cache": kv_cache, "pool": pool, "scratch": scratch,
                "mb": mgr.mb, "rng": rng}
    kv_cache = jax.eval_shape(lambda: kvstate.init_kv_cache(
        cfg, ecfg.max_batch, ecfg.max_seq_len))
    return {"kv_cache": kv_cache, "pool": {}, "scratch": {}, "mb": 0,
            "rng": rng}
