"""Decoder-only transformer core shared by every served family: Llama,
Gemma, Mixtral (sparse experts) and the looped decoder (``loop_steps``
passes over one set of layers, four norms a layer, an exit gate).

Functional style: ``init_decoder`` builds a param pytree (nested dicts with
stable path names the sharding rules in ``tpu9.parallel.sharding`` pattern-
match), ``decoder_forward`` runs prefill/train/decode from the same code path
with static shapes (XLA traces one graph per (batch, seq) bucket).

Weight layout is MXU-friendly: all projections stored as [in, out] so the
forward pass is plain ``x @ w`` row-major matmuls in bf16.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.norms import rms_norm
from ..ops.quant import maybe_matmul, project_heads
from ..ops.rotary import apply_rope, rope_rows
from . import hybrid, kvstate, shortconv, ssm
from .hybrid import dense_init

Params = dict[str, Any]


@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 14336
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    # what the families differ in: descriptors of the architecture, read
    # by ``init_decoder`` and ``decoder_forward``; no model is named
    # silu (llama) | gelu (gemma) | relu2 (a list's ungated experts alone)
    act: str = "silu"
    norm_offset: float = 0.0       # 1.0 for gemma's (1+w) RMSNorm
    embed_scale: bool = False      # gemma scales embeddings by sqrt(dim)
    logit_softcap: float = 0.0     # gemma-2 style; 0 = off
    tie_embeddings: bool = False   # output head = embed^T
    # sparse-MoE FFN (mixtral family): n_experts 0 = dense
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # looped decoder: the layers run ``loop_steps`` times a token over ONE
    # set of weights, each pass with keys and values of its own and closed
    # by the final norm (1 = a plain decoder)
    loop_steps: int = 1
    # four norms a layer: a second one on each sub-layer's OUTPUT, inside
    # the residual branch
    sandwich_norm: bool = False
    # a learned gate after every pass: the head reads the state of the
    # first pass at which the gates' cumulative exit probability reaches
    # ``exit_threshold``, else the last pass's
    exit_gate: bool = False
    exit_threshold: float = 1.0
    # attention that is exact inside a window of ``attn_window`` positions
    # (block-diagonal: it does not slide) and reads one summary for every
    # ``attn_chunk`` positions of every earlier window (``ops.
    # summary_attention``); 0 = plain causal attention. The cache then holds
    # ``attn_window / attn_chunk`` entries a closed window, not one a token
    # (``kv_entry``), and the residual stream is carried in float32
    attn_window: int = 0
    attn_chunk: int = 0
    # a layer PATTERN (``models.hybrid``): which layers are latent attention
    # (MLA) over a cache of one row a token and which delta-rule linear
    # attention (KDA) over a state a LANE that no cache holds. Of every
    # ``layer_group`` layers the last is MLA and the others KDA, so 1 is MLA
    # in every layer (no KDA layer, no state a lane); 0 = every layer is the
    # plain attention above. However the pattern is spelled, ``layers`` is
    # the one statement of what layer ``l`` is
    layer_group: int = 0
    # latent attention's widths: the cached latent, a head's unrotated and
    # rotated query/key parts, a head's value
    mla_latent: int = 0
    mla_nope: int = 0
    mla_rope: int = 0
    mla_v: int = 0
    # its query: through a latent of ``mla_q_latent`` numbers with a norm of
    # its own (0 = one full-rank matrix); a sigmoid gate a head on its
    # output; the softmax scale times ``mla_mscale`` squared (YaRN's
    # attention temperature)
    mla_q_latent: int = 0
    mla_out_gate: bool = True
    mla_mscale: float = 1.0
    # YaRN positions: ``(factor, original_max_positions, beta_fast,
    # beta_slow)`` (``ops.rotary.yarn_inv_freq``); () = ``rope_theta`` alone
    rope_yarn: tuple = ()
    # KDA: taps of the short causal convolution on q, k, v, and the lower
    # bound of a token's log-decay (a negative number)
    kda_conv: int = 0
    kda_gate_bound: float = 0.0
    # the expert layer's own descriptors (``n_experts`` > 0). The first
    # ``moe_dense_layers`` layers keep the dense FFN of ``hidden_dim``; an
    # expert is ``moe_hidden_dim`` wide (0 = ``hidden_dim``). The rest is
    # ``models.moe.MoeConfig``'s: ``moe_routed`` experts routed over of which
    # ``n_experts`` are HELD from global id ``moe_held_first`` on (0 = all
    # held), one shared expert of ``moe_shared_dim``, the score function, a
    # bias in the choice only, groups, renormalised gates, their scale
    moe_dense_layers: int = 0
    moe_hidden_dim: int = 0
    moe_routed: int = 0
    moe_held_first: int = 0
    moe_shared_dim: int = 0
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_groups: int = 0
    moe_top_groups: int = 0
    moe_renormalise: bool = True
    moe_gate_scale: float = 1.0
    # a layer pattern stated as a LIST (``models.ssm``): the attention kind
    # of every layer, ``"ssm"`` (a Mamba-2 state-space mixer over a state a
    # LANE that no cache holds) or ``"full"`` (the plain attention above,
    # over per-head rows of a pool only as deep as there are such layers);
    # () = no list. ``layer_group`` is the one RULE (KDA closed by MLA) and
    # stays what it was; ``layers`` is built from whichever is stated
    layer_pattern: tuple = ()
    # the state-space mixer's sizes: heads, a head's width, the state's
    # width, the groups that share ``B`` and ``C``, the taps of the short
    # causal convolution (with a bias) on ``x``, ``B`` and ``C``
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 0
    # the groups of the mixer's gated norm: the norm runs over each group's
    # ``ssm_heads * ssm_head_dim / ssm_norm_groups`` channels (1 = over all)
    ssm_norm_groups: int = 1
    # the OTHER half of a listed layer, stated as a list beside
    # ``layer_pattern``: the feed-forward kind of every layer, ``"experts"``
    # or ``"none"``; () = the rule above (``moe_dense_layers`` dense, then
    # experts where there are any). With it ``layer_pattern`` says ``"none"``
    # too, and every layer is ONE sub-layer — a mixer alone, attention alone
    # or an expert layer alone, ``x + f(norm(x))`` with one norm and no
    # weights for the half it lacks
    ffn_pattern: tuple = ()
    # the expert layer of a listed pattern: experts of two matrices,
    # ``act(x W_up) W_down`` (False; True = the gated three), with the
    # shared expert of the same form; and routed experts that work in a
    # LATENT of ``moe_latent_dim`` numbers — one projection down in front of
    # the dispatch and one up behind the combine, shared by all experts,
    # while the router and the shared expert read the full width (0 = none)
    moe_gated: bool = True
    moe_latent_dim: int = 0
    # plain attention without positions (False: no rotary, no angles), and
    # at a softmax scale of its own (0 = ``head_dim ** -0.5``)
    rope: bool = True
    attn_scale: float = 0.0
    # multipliers (1 = off): on the embeddings, on what every sub-layer adds
    # to the stream, and the divisor of the logits
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    logit_div: float = 1.0
    # a third kind of a listed layer, ``"conv"`` (``models.shortconv``): a
    # gated short convolution of ``conv_taps`` taps a channel, whose whole
    # state is the last ``conv_taps - 1`` rows it convolved — kept a LANE and,
    # for the prefix cache, a BLOCK (``models.kvstate``). Such a list is of
    # WHOLE layers (no ``ffn_pattern``): a mixer or rotary attention, then
    # the rule's feed-forward part — ``moe_dense_layers`` dense, then expert
    # layers that hold every expert they route over (0 = no such layer)
    conv_taps: int = 0
    # an RMSNorm a head, with a weight, on the plain attention's queries and
    # keys before the rotation (a list with ``"conv"`` layers alone)
    qk_norm: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps={self.loop_steps}: at least 1")
        if self.sandwich_norm and self.n_experts:
            raise ValueError("sandwich_norm with experts: no served model "
                             "has both, and the expert branch is not built "
                             "with a norm on its output")
        if self.exit_gate and self.exit_threshold != 1.0:
            raise ValueError(
                f"exit_threshold={self.exit_threshold}: only 1.0 is built. "
                "Below it sequences of one batch stop after different "
                "passes: the decode programs have no per-sequence pass "
                "count, the paged pool no keys and values for the passes "
                "a token skipped (later tokens read them), and the "
                "scheduler prices every step alike")
        if self.attn_window or self.attn_chunk:
            if (self.attn_window <= 0 or self.attn_chunk <= 0
                    or self.attn_window % self.attn_chunk):
                raise ValueError(
                    f"attn_window={self.attn_window}, attn_chunk="
                    f"{self.attn_chunk}: a window is a whole number of "
                    "chunks, because a summary stands for one whole chunk "
                    "of a closed window")
            if self.n_experts or self.looped:
                raise ValueError(
                    "attn_window with experts or a pass loop: no served "
                    "model has both; the window's summarise is not built "
                    "for a cache of loop_steps planes a layer, and no "
                    "expert layer was ever run over a float32 stream")

        # what the layers ARE, once an instance and however they were
        # spelled (derived: no field, so no part of equality or the hash)
        object.__setattr__(self, "layers", _layer_list(self))
        ssm.refuse_unbuilt_list(self)
        if self.layer_group:
            hybrid.refuse_unbuilt_pattern(self)
        elif (self.mla_latent or self.kda_conv or self.mla_q_latent
              or self.rope_yarn or self.mla_mscale != 1.0
              or (self.moe_dense_layers and not self.conv_taps)
              or ((self.moe_routed or self.moe_shared_dim
                   or self.moe_score != "softmax")
                  and "experts" not in self.ffn_pattern
                  and not self.conv_taps)):
            raise ValueError(
                "latent attention (its query latent, YaRN positions and "
                "temperature with it), the delta rule and the expert "
                "layer's share, shared expert and sigmoid gates are built "
                "for a layer pattern only (layer_group > 0, a listed "
                "pattern whose ffn_pattern says which layers are expert "
                "layers, or a list of whole layers around short "
                "convolutions): no served model has one without the other")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    # -- what layer ``l`` is ---------------------------------------------------
    # ``layers`` (``_layer_list``) is THE statement: a tuple of ``(attention,
    # ffn)``, one a layer. Everything below reads it and nothing else; the
    # named questions are the closed set of what a program, the state's
    # format and the engine may ask of the pattern, each with its rule here.

    def layer_kind(self, l: int) -> tuple:
        """``(attention, ffn)`` of layer ``l`` (0-based): attention is
        ``"full"`` (the plain attention of a uniform decoder), ``"kda"``,
        ``"mla"``, ``"ssm"``, ``"conv"`` or ``"none"``; the ffn ``"dense"``,
        ``"experts"`` or ``"none"`` (a layer of one half)."""
        return self.layers[l]

    def layers_of(self, attention: str) -> tuple:
        """The layers whose attention is of this kind, in order: layer
        ``layers_of(kind)[p]`` keeps plane ``p`` of that kind's state."""
        return tuple(l for l, (kind, _) in enumerate(self.layers)
                     if kind == attention)

    @property
    def uniform(self) -> bool:
        """Whether every layer is the plain attention: the decoder that
        takes a mesh, a balance loss and a pass loop. Otherwise the layers
        are a PATTERN: its forward pass is told the live rows (``n_valid``)
        and returns the experts its tokens chose."""
        return all(kind == "full" for kind, _ in self.layers)

    @property
    def latent_rows(self) -> bool:
        """Whether a cache row is one latent for all heads: latent
        attention's, alone or closing groups of KDA layers."""
        return any(kind in ("kda", "mla") for kind, _ in self.layers)

    @property
    def kv_row(self) -> tuple:
        """``((heads, width) of a "k" row, of a "v" row)`` of the cache, a
        token a layer. Latent attention keeps ONE latent for all heads under
        "k" and its one rotated key under "v": ``mla_latent + mla_rope``
        numbers a token."""
        if self.latent_rows:
            return (1, self.mla_latent), (1, self.mla_rope)
        pack = self.kv_pack
        return ((self.n_kv_heads // pack, self.head_dim * pack),) * 2

    @property
    def kv_pack(self) -> int:
        """KV heads a cache row holds side by side (1 = a head a row).
        Worked out, not stated: per-head rows BESIDE state a lane (new with
        the packing, PR 55) hold as many narrow heads as fill a row
        (``models.kvstate.heads_per_row``). Everything else keeps a head a
        row: latent rows have no heads, a window's summarise reads a head a
        row, and a uniform decoder's programs stay what they were (the int8
        pool's scale is one a (token, head), which a packed row would share;
        beside state a lane the engine refuses ``kv_quant`` and a mesh)."""
        if self.latent_rows or not self.lane_state:
            return 1
        return kvstate.heads_per_row(self.head_dim, self.n_kv_heads)

    @property
    def lane_state(self) -> tuple:
        """The attention kinds of this decoder that keep state by LANE
        (``models.kvstate.lane_shapes``), in layer order of first use; ()
        for a decoder whose whole state is paged."""
        kinds = {kind for kind, _ in self.layers}
        return tuple(k for k in ("kda", "ssm", "conv") if k in kinds)

    @property
    def wide_stream(self) -> bool:
        """Whether the residual stream is carried in float32 while every
        sub-layer computes in the embeddings' type: under ``attn_window``,
        in the pass loop (``_looped_passes`` says why), and for per-head
        rows beside state a lane — eighty branches times ``residual_mult``
        each round a bfloat16 stream whole, which was a third of the served
        program's distance from the float32 reference (PERF.md section 6,
        PR 55)."""
        return bool(self.attn_window or self.looped
                    or (self.lane_state and not self.latent_rows))

    @property
    def pattern_label(self) -> str:
        """The pattern as it was stated, for a refusal's message alone."""
        return f"layer_group={self.layer_group}" if self.layer_group \
            else "layer_pattern"

    @property
    def looped(self) -> bool:
        """Whether the forward pass is the pass loop (and a decode program
        returns the exit pass beside each token)."""
        return self.loop_steps > 1 or self.exit_gate

    @property
    def kv_layers(self) -> int:
        """Depth of the KV state: a token owns one plane of keys and
        values per (pass, layer) that attends over rows — a pattern's
        latent-attention or plain-attention layers alone — although the
        weights have ``n_layers``."""
        return len(self.layers_of("mla") + self.layers_of("full")) \
            * self.loop_steps

    # -- where a token lives in its cache -----------------------------------
    # ``pos // block`` used to be the place of a token in its cache
    # everywhere. It is ``kv_entry(pos) // block`` now: the three functions
    # below are the one place that knows the rule, they take ints, numpy and
    # jax arrays alike, and for plain attention each returns its argument
    # itself, so that a plain program lowers as it did.

    @property
    def window_entries(self) -> int:
        """Cache entries a CLOSED window keeps: one a chunk."""
        return self.attn_window // self.attn_chunk if self.attn_window else 0

    def kv_entry(self, pos):
        """The cache entry of the token at position ``pos``: the summaries
        of the windows before its own come first, then its place in its
        window."""
        if not self.attn_window:
            return pos
        return (self.window_entries * (pos // self.attn_window)
                + pos % self.attn_window)

    def kv_entries(self, n):
        """Entries a sequence of ``n`` tokens holds (what attention masks
        by): one past its last token's. A window is summarised when the
        NEXT one opens, so this is not ``kv_entry(n)``."""
        if not self.attn_window:
            return n
        return (self.kv_entry(n - 1) + 1) * (n > 0)

    def kv_entries_peak(self, n: int) -> int:
        """The most entries a sequence addresses on its way to ``n`` tokens
        (what a reservation, a table and the prefill scratch are sized by):
        a window is at its widest just before it closes."""
        if not self.attn_window or n <= self.attn_window:
            return n
        last = (n - 1) // self.attn_window
        return max(self.kv_entry(last * self.attn_window - 1) + 1,
                   self.kv_entry(n - 1) + 1)


def _layer_list(cfg: DecoderConfig) -> tuple:
    """``DecoderConfig.layers``: ``(attention, ffn)`` of every layer, from
    whichever spelling the constructor was given — the rule (``layer_group``:
    of every group the last layer ``"mla"``, the others ``"kda"``), the list
    (``layer_pattern``), the two lists (``ffn_pattern`` beside it) or none
    (every layer ``"full"``); without an ``ffn_pattern`` the first
    ``moe_dense_layers`` layers are ``"dense"`` and the rest ``"experts"``
    where there are any. The one reader of the spelling beside the refusals
    ``__post_init__`` calls next, which turn away a spelling that is wrong
    (a list of another length or of unknown words, both a rule and a list,
    an ``ffn_pattern`` alone) in the order they always did: tests pin which
    refusal a configuration with several faults meets first."""
    rule, n = cfg.layer_group, cfg.n_layers
    attention = cfg.layer_pattern or tuple(
        ("mla" if (l + 1) % rule == 0 else "kda") if rule else "full"
        for l in range(n))
    ffn = cfg.layer_pattern and cfg.ffn_pattern or tuple(
        "experts" if cfg.n_experts and l >= cfg.moe_dense_layers else "dense"
        for l in range(n))
    return tuple(zip(attention, ffn))


def init_decoder(rng: jax.Array, cfg: DecoderConfig) -> Params:
    keys = iter(jax.random.split(rng, cfg.n_layers * 7 + 3))
    dt = cfg.dtype
    params: Params = {
        # (a table that is fed in times ``embed_mult`` is seeded that much
        # smaller, so that the stream starts where an unscaled table's
        # does: at 0.02 x 12 a token's own row of a TIED head outweighs
        # every other logit and a seeded model only repeats its input)
        "embed": (jax.random.normal(next(keys), (cfg.vocab_size, cfg.dim),
                                    dtype=jnp.float32)
                  * (0.02 / cfg.embed_mult)).astype(dt),
        "final_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(keys), cfg.dim, cfg.vocab_size,
                                       dt)
    else:
        next(keys)
    if cfg.exit_gate:
        params["exit_gate"] = init_exit_gate(rng, cfg)

    if not cfg.uniform:
        # a pattern's layers have trees of their own; their rngs are folded
        # from the tree's, as every later addition's are
        params["layers"] = [
            init_pattern_layer(jax.random.fold_in(rng, li), cfg, li)
            for li in range(cfg.n_layers)]
        return params
    q_dim = cfg.n_heads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    for li in range(cfg.n_layers):
        layer = {
            "attn_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
            "mlp_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
            "wq": dense_init(next(keys), cfg.dim, q_dim, dt),
            "wk": dense_init(next(keys), cfg.dim, kv_dim, dt),
            "wv": dense_init(next(keys), cfg.dim, kv_dim, dt),
            "wo": dense_init(next(keys), q_dim, cfg.dim, dt),
        }
        if cfg.n_experts:
            from .moe import init_moe_layer
            layer["moe"] = init_moe_layer(
                jax.random.fold_in(next(keys), li), moe_cfg(cfg))
            next(keys), next(keys)   # the rng schedule stays the dense one
        else:
            layer.update(_init_dense_ffn(keys, cfg))
        layer.update(init_post_norms(cfg))
        layer.update(init_summary_vectors(jax.random.fold_in(rng, li), cfg))
        params["layers"].append(layer)
    return params


def _init_dense_ffn(r, cfg: DecoderConfig) -> Params:
    """The dense SwiGLU's three matrices, their rngs drawn from ``r``."""
    d, hidden, dt = cfg.dim, cfg.hidden_dim, cfg.dtype
    return {"w_gate": dense_init(next(r), d, hidden, dt),
            "w_up": dense_init(next(r), d, hidden, dt),
            "w_down": dense_init(next(r), hidden, d, dt)}


def init_pattern_layer(rng: jax.Array, cfg: DecoderConfig, l: int) -> Params:
    """Layer ``l`` of a pattern, seeded: a norm for each half it has, its
    attention kind's tree (what a seed has to choose for a kind is said where
    the kind is: ``hybrid.init_kda`` / ``init_mla``, ``ssm.init_mixer``,
    ``shortconv.init_conv_mixer``), then its feed-forward part. Every kind
    draws from the one split in its own order, and the split is the width
    its family always had (16 for the latent family's kinds, 12 for a
    list's): a seeded tree is part of what the benchmark serves."""
    attention, ffn = cfg.layers[l]
    d, dt = cfg.dim, cfg.dtype
    r = iter(jax.random.split(rng, 16 if attention in ("kda", "mla") else 12))
    # (a half-layer keeps the norm of the half it has, and no other)
    layer = {name: jnp.ones((d,), jnp.float32)
             for name, half in (("attn_norm", attention), ("mlp_norm", ffn))
             if half != "none"}
    if attention == "kda":
        layer["kda"] = hybrid.init_kda(r, cfg)
    elif attention == "mla":
        layer["mla"] = hybrid.init_mla(r, cfg)
    elif attention == "ssm":
        layer["ssm"] = ssm.init_mixer(r, cfg)
    elif attention == "conv":
        layer["conv"] = shortconv.init_conv_mixer(next(r), cfg)
    elif attention == "full":
        q_dim, kv_dim = cfg.n_heads * cfg.head_dim, \
            cfg.n_kv_heads * cfg.head_dim
        layer.update(wq=dense_init(next(r), d, q_dim, dt),
                     wk=dense_init(next(r), d, kv_dim, dt),
                     wv=dense_init(next(r), d, kv_dim, dt),
                     wo=dense_init(next(r), q_dim, d, dt))
        if cfg.qk_norm:
            layer.update(q_norm=jnp.ones((cfg.head_dim,), jnp.float32),
                         k_norm=jnp.ones((cfg.head_dim,), jnp.float32))
    if ffn == "experts":
        from .moe import init_moe_layer
        layer["moe"] = init_moe_layer(next(r), moe_cfg(cfg))
    elif ffn == "dense":
        layer.update(_init_dense_ffn(r, cfg))
    return layer


def init_summary_vectors(rng: jax.Array, cfg: DecoderConfig) -> Params:
    """The two vectors a head that weigh a chunk's tokens into its summary
    (``attn_window``; none otherwise). Their rng is folded from the tree's
    own, so the schedule of every other leaf is the plain decoder's."""
    if not cfg.attn_window:
        return {}
    from ..ops.summary_attention import init_vectors
    return init_vectors(rng, cfg.n_kv_heads, cfg.head_dim)


def init_post_norms(cfg: DecoderConfig) -> Params:
    """The two extra norm vectors of a ``sandwich_norm`` layer (none
    otherwise). They start at ``1 / sqrt(2 n_layers)``, the scaled
    residual initialisation: a norm on a branch's OUTPUT fixes what the
    branch adds to the stream, and at 1.0 each of ``2 n_layers`` branches
    would add as much as the stream holds — seeded weights would then be a
    chaotic map that amplifies a rounding of 2 % after one pass to 17-51 %
    after four (width 256; PERF.md, PR 34). A checkpoint brings its own."""
    if not cfg.sandwich_norm:
        return {}
    gain = (2.0 * cfg.n_layers) ** -0.5
    return {name: jnp.full((cfg.dim,), gain, jnp.float32) - cfg.norm_offset
            for name in ("attn_post_norm", "mlp_post_norm")}


def init_exit_gate(rng: jax.Array, cfg: DecoderConfig) -> Params:
    """The exit gate: one logit a position, ``w . h + b``, float32 like
    the norm vectors. Its rng is folded from the tree's own, so the
    schedule of every other leaf is the plain decoder's."""
    w = dense_init(jax.random.fold_in(rng, cfg.dim), cfg.dim, 1, jnp.float32)
    return {"w": w[:, 0], "b": jnp.zeros((1,), jnp.float32)}


def moe_cfg(cfg: DecoderConfig):
    """The expert layer's own config (``models.moe.MoeConfig``) of ``cfg``."""
    from .moe import MoeConfig
    return MoeConfig(dim=cfg.dim,
                     hidden_dim=cfg.moe_hidden_dim or cfg.hidden_dim,
                     n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                     capacity_factor=cfg.moe_capacity_factor,
                     act=cfg.act, dtype=cfg.dtype,
                     n_routed=cfg.moe_routed, held_first=cfg.moe_held_first,
                     shared_dim=cfg.moe_shared_dim, score=cfg.moe_score,
                     select_bias=cfg.moe_select_bias,
                     n_groups=cfg.moe_groups, top_groups=cfg.moe_top_groups,
                     renormalise=cfg.moe_renormalise,
                     gate_scale=cfg.moe_gate_scale, gated=cfg.moe_gated,
                     latent_dim=cfg.moe_latent_dim)


def _act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


# Device scopes (ISSUE 24): the ``jax.named_scope`` names the forward pass
# runs under — they reach every HLO instruction's ``op_name``, so a
# profiler trace's device time can be summed by part of the model.
# ``serving.graphs`` adds ``kv.gather``/``kv.splice`` around the pool
# plumbing; norms, the rng split and the length update carry none. Since
# ISSUE 25 carries the cache whole, nothing runs under ``kv.pack`` and
# ``kv.slice`` is only the read of a dense cache at its layer — both stay
# declared, because the benchmark's readers sum the ``kv.*`` names.
DEVICE_SCOPES = ("embed", "attn.qkv", "attn.rope", "kv.slice", "kv.write",
                 "kv.pack", "kv.gather", "kv.splice", "attn.core",
                 "attn.out", "ffn", "moe.route", "moe.experts",
                 "moe.combine", "head", "sample")
# A looped decoder's pass-closing norm, exit gate and selection (ISSUE 34);
# no plain program runs anything under them. Apart from ``DEVICE_SCOPES``:
# the benchmark's accepted tests pin what that tuple leaves ungrouped.
LOOP_SCOPES = ("loop.norm", "loop.gate", "loop.select")
# An expert layer whose routed experts work in a latent (``moe_latent_dim``):
# the projection down in front of the dispatch and the one up behind the
# combine. Apart for the same reason (``moe.shared`` is ``hybrid.
# HYBRID_SCOPES``')
LATENT_MOE_SCOPES = ("moe.latent.in", "moe.latent.out")
# The summarise of a closed window (``attn_window``, ISSUE 46), in every
# program that runs it: ``serving.graphs`` opens the scope. Apart for the
# same reason.
SUMMARY_SCOPES = ("kv.summarise",)


def _pre_norm(x: jnp.ndarray, weight: jnp.ndarray, cfg: DecoderConfig,
              compute_dtype):
    """A sub-layer's input norm. ``compute_dtype`` is what the sub-layer
    computes in where the residual stream is carried wider than that (a
    looped decoder's float32 stream); None: the stream's own type."""
    h = rms_norm(x, weight, cfg.norm_eps, cfg.norm_offset)
    return h if compute_dtype is None else h.astype(compute_dtype)


def _attn_block(layer: Params, x: jnp.ndarray, cfg: DecoderConfig,
                positions: jnp.ndarray, sin, cos,
                kv_cache: Optional[Params], layer_idx: int,
                cache_len: Optional[jnp.ndarray], decode: bool,
                mesh=None, compute_dtype=None, n_valid=None, cache_base=0):
    """Layer ``layer_idx``'s attention, by its kind. The plain attention:
    project, rotate, ``kvstate.write``, ``kvstate.attend``, project out; the
    other kinds in their modules. Returns ``(x, kv_cache)``: the cache dict
    is carried WHOLE from layer to layer — this layer's k/v (or its state a
    lane) are written into the ``[L, ...]`` arrays in place and read at the
    layer's PLANE, ``cache_base`` plus its place among the layers of its
    kind; nothing is sliced out and re-stacked."""
    b, t, _ = x.shape
    kind = cfg.layers[layer_idx][0]
    # (a uniform decoder's is ``cache_base + layer_idx``: a Python int where
    # the base is one)
    plane = cache_base + cfg.layers_of(kind).index(layer_idx)
    h = _pre_norm(x, layer["attn_norm"], cfg, compute_dtype)
    if kind != "full":
        if kind == "kda":
            y, kv_cache = hybrid.kda_block(layer["kda"], h, cfg, kv_cache,
                                           plane, decode, n_valid)
        elif kind == "mla":
            y, kv_cache = hybrid.mla_block(
                layer["mla"], h, cfg, positions, sin, cos, kv_cache, plane,
                cache_len, decode)
        elif kind == "ssm":
            y, kv_cache = ssm.ssm_block(layer["ssm"], h, cfg, kv_cache,
                                        plane, decode, n_valid)
        else:
            y, kv_cache = shortconv.conv_block(
                layer["conv"], h, cfg, kv_cache, plane, decode, n_valid,
                positions)
        return _residual(x, y, cfg), kv_cache
    with jax.named_scope("attn.qkv"):
        q = project_heads(h, layer["wq"], cfg.n_heads, cfg.head_dim)
        k = project_heads(h, layer["wk"], cfg.n_kv_heads, cfg.head_dim)
        v = project_heads(h, layer["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # over each head's own numbers, before the rotation
        with jax.named_scope("attn.qk_norm"):
            q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
            k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if cfg.rope:
        with jax.named_scope("attn.rope"):
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
    if cfg.attn_scale:
        # the kernels fix ``head_dim ** -0.5``: the queries carry the rest,
        # a power of two (``ssm.refuse_unbuilt_list``), so this product
        # rounds nothing. (Where heads are packed to a row, below,
        # ``kvstate.pack_heads`` multiplies the queries by ``sqrt(kv_pack)``
        # too, in float32, and rounds them ONCE to the model's type: a
        # bfloat16 rounding of q that a head a row does not have.)
        with jax.named_scope("attn.qkv"):
            q = q * jnp.asarray(cfg.attn_scale * cfg.head_dim ** 0.5, q.dtype)
    packed = cfg.kv_pack > 1 and kv_cache is not None
    if packed:
        # the cache keeps ``kv_pack`` heads a row: keys and values as they
        # lie, the queries widened to a row (zero where the row holds
        # another head) and carrying ``sqrt(kv_pack)``, because the kernels
        # then fix ``(kv_pack head_dim) ** -0.5``
        with jax.named_scope("attn.qkv"):
            q, k, v = kvstate.pack_heads(q, k, v, cfg.kv_pack)

    # rotary keeps the position; the cache is addressed, and attention is
    # masked, by ENTRY (the same arrays for plain attention)
    entries = cfg.kv_entry(positions)
    if cache_len is not None:
        cache_len = cfg.kv_entries(cache_len)
    if cfg.attn_window and kv_cache is not None and (
            not kvstate.is_paged(kv_cache)
            and (decode or cache_len is None)):
        raise NotImplementedError(
            "attn_window over a dense cache is built for chunked prefill "
            "alone (the paged engine's scratch): a dense decode cache has "
            "no program that summarises a closed window")

    if kv_cache is None:
        with jax.named_scope("attn.core"):
            if cfg.attn_window:
                from ..ops.summary_attention import windowed_summary_attention
                out = windowed_summary_attention(
                    q, k, v, layer["summary_mu"], layer["summary_phi"],
                    cfg.attn_window, cfg.attn_chunk)
            else:
                out = attention(q, k, v, causal=True, mesh=mesh)
    else:
        # whatever form the cache has (``models.kvstate``): this layer's
        # keys and values go where their entries say, then its queries
        # attend over what is written
        kv_cache = kvstate.write(kv_cache, plane, k, v, entries, decode)
        out = kvstate.attend(kv_cache, plane, q, k, v, entries,
                             cache_len, decode, mesh)

    with jax.named_scope("attn.out"):
        if packed:
            out = kvstate.unpack_heads(out, cfg.n_kv_heads, cfg.kv_pack)
        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        if cfg.sandwich_norm:
            return x + rms_norm(maybe_matmul(out, layer["wo"]),
                                layer["attn_post_norm"], cfg.norm_eps,
                                cfg.norm_offset), kv_cache
        return _residual(x, maybe_matmul(out, layer["wo"]), cfg), kv_cache


def _residual(x, y, cfg: DecoderConfig):
    """The stream with a sub-layer's output added, times
    ``residual_mult`` where that is stated."""
    if cfg.residual_mult == 1.0:
        return x + y
    return x + y.astype(x.dtype) * jnp.asarray(cfg.residual_mult, x.dtype)


def _mlp_block(layer: Params, x: jnp.ndarray, cfg: DecoderConfig,
               compute_dtype=None, serving: bool = False, mesh=None,
               live=None):
    h = _pre_norm(x, layer["mlp_norm"], cfg, compute_dtype)
    if cfg.moe_routed and "moe" in layer:
        # an expert layer that is told which experts it holds: dropless at
        # every width, the held experts its LIVE rows (``live`` bool [B, T];
        # None: all) picked over few rows and the sorted form with local ids
        # over many; ``aux`` is the experts its tokens chose
        from .moe import SORTED_MIN_TOKENS, moe_ffn_held, moe_ffn_sorted
        if h.shape[0] * h.shape[1] > SORTED_MIN_TOKENS:
            y, picks = moe_ffn_sorted(layer["moe"], h, moe_cfg(cfg))
        else:
            y, picks = moe_ffn_held(layer["moe"], h, moe_cfg(cfg), live)
        with jax.named_scope("moe.combine"):
            return x + y, {"picks": picks}
    if cfg.n_experts and not cfg.moe_routed:
        from .moe import (moe_ffn, moe_ffn_held, moe_ffn_sorted,
                          takes_held_form, takes_sorted_form)
        # one algorithm, dropless top-k, in the form that is cheapest for
        # the rows of this call (a serving step keeps no router statistics):
        # a decode step — it says which rows are LIVE — reads the experts
        # they picked and says which (``aux``, as an expert layer that is
        # told what it holds does), a wide call sorts its rows by expert
        n_tokens = h.shape[0] * h.shape[1]
        if serving and takes_held_form(layer["moe"], n_tokens, live, mesh):
            y, picks = moe_ffn_held(layer["moe"], h, moe_cfg(cfg), live)
            aux = {"picks": picks}
        elif serving and takes_sorted_form(layer["moe"], n_tokens, mesh):
            y, aux = moe_ffn_sorted(layer["moe"], h, moe_cfg(cfg)), None
        else:
            y, aux = moe_ffn(layer["moe"], h, moe_cfg(cfg),
                             ep_sharded=False)
        with jax.named_scope("moe.combine"):
            return x + y, aux
    with jax.named_scope("ffn"):
        gated = _act(maybe_matmul(h, layer["w_gate"]), cfg.act) \
            * maybe_matmul(h, layer["w_up"])
        if cfg.sandwich_norm:
            return x + rms_norm(maybe_matmul(gated, layer["w_down"]),
                                layer["mlp_post_norm"], cfg.norm_eps,
                                cfg.norm_offset), None
        return _residual(x, maybe_matmul(gated, layer["w_down"]), cfg), None


def _live_rows(n_valid, t: int):
    """``n_valid`` int32 [B], how many of each row's ``t`` tokens are real,
    as a mask bool [B, t]; None: no one says, None."""
    return None if n_valid is None else jnp.arange(t) < n_valid[:, None]


def _layers(params: Params, x, cfg: DecoderConfig, positions, sin, cos,
            kv_cache, cache_base, cache_len, decode: bool, mesh,
            moe_balance, compute_dtype=None, n_valid=None):
    """Every layer once, each half it has (``cfg.layers``; ``"none"`` is the
    half a listed layer lacks). The planes a layer keeps start at
    ``cache_base``: 0 for a decoder without a pass loop (a plane is then a
    Python int, as it always was), a traced ``u * n_layers`` inside the loop.
    ``n_valid`` int32 [B]: how many of each row's tokens are real, where the
    caller says (a padded chunk tail, an idle decode lane) — they alone
    advance state a lane, and an expert layer at few rows reads the experts
    THEY picked; None: all. Returns ``(x, kv_cache, moe_balance, picks)``:
    ``picks`` the experts every token chose in the expert layers that say
    so, int32 [B, T, layers, top_k] (global ids; a padded row's are whatever
    its padding chose: the caller knows which rows are real), or None."""
    b, t, _ = x.shape
    live = _live_rows(n_valid, t)
    if n_valid is None and not cfg.uniform:
        # (a pattern's blocks are always told; a uniform decoder's trace
        # keeps no constant that nothing reads)
        n_valid = jnp.full((b,), t, jnp.int32)
    picks = []
    for i, (layer, (attention, ffn)) in enumerate(
            zip(params["layers"], cfg.layers)):
        if attention != "none":
            x, kv_cache = _attn_block(layer, x, cfg, positions, sin, cos,
                                      kv_cache, i, cache_len, decode, mesh,
                                      compute_dtype, n_valid, cache_base)
        if ffn == "none":
            continue
        x, aux = _mlp_block(layer, x, cfg, compute_dtype,
                            serving=kv_cache is not None, mesh=mesh,
                            live=live)
        if aux is None:
            continue
        if "picks" in aux:
            picks.append(aux["picks"])
        else:
            moe_balance = moe_balance + aux["balance_loss"]
    return x, kv_cache, moe_balance, \
        jnp.stack(picks, axis=2) if picks else None


def _looped_passes(params: Params, x, cfg: DecoderConfig, positions, sin,
                   cos, kv_cache, cache_len, decode: bool, mesh,
                   moe_balance):
    """``loop_steps`` passes over the one set of layers as a DEVICE loop:
    the layer bodies are traced once whatever the number of passes. Pass
    ``u`` reads and writes planes ``[u * n_layers, (u + 1) * n_layers)`` of
    the cache, the final norm closes every pass and its output is what the
    next pass starts from. With an exit gate the carry also holds the
    probability that no earlier pass exited, the selected state and its
    pass: ``selected`` is the state of the first pass at which the
    cumulative exit probability reaches the threshold, else the last
    pass's. Every pass always runs — later tokens read its keys and
    values. Returns ``(selected, kv_cache, moe_balance, exit_info)``,
    ``exit_info`` int32 ``[B, T, 2]``: the pass whose state the head reads,
    and the passes the loop RAN (counted in the loop's own carry, so a loop
    that one day stops early says so itself).

    The residual stream is carried in float32 and every sub-layer still
    computes in the type the embeddings came in: a branch adds a fraction
    of what the stream holds, so rounding the SUM to bfloat16 at every add
    costs several times what rounding the branch does, and the same layers
    applied ``loop_steps`` times carry the error on (at width 256 the
    logits' error against float32 after four passes was 3.5-4.6 % of their
    spread with a bfloat16 stream and 2-3 % with this one; PERF.md, PR 34).
    """
    b, t, _ = x.shape
    last = cfg.loop_steps - 1
    compute_dtype = x.dtype
    x = x.astype(jnp.float32)

    def one_pass(u, carry):
        x, kv, balance, remaining, cdf, selected, exit_step, ran = carry
        ran = ran + 1
        x, kv, balance, _ = _layers(params, x, cfg, positions, sin, cos, kv,
                                    u * cfg.n_layers, cache_len, decode,
                                    mesh, balance, compute_dtype)
        with jax.named_scope("loop.norm"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                         cfg.norm_offset)
        if not cfg.exit_gate:
            return x, kv, balance, remaining, cdf, x, exit_step, ran
        with jax.named_scope("loop.gate"):
            gate = params["exit_gate"]
            lam = jax.nn.sigmoid(
                jnp.sum(x.astype(jnp.float32) * gate["w"], axis=-1)
                + gate["b"][0])
        with jax.named_scope("loop.select"):
            # p_u = lam_u * prod_{j<u}(1 - lam_j), what remains at the
            # last pass; ``cdf`` is their running sum, as published
            cdf = cdf + jnp.where(u == last, remaining, lam * remaining)
            take = (exit_step < 0) & ((cdf >= cfg.exit_threshold)
                                      | (u == last))
            selected = jnp.where(take[..., None], x, selected)
            exit_step = jnp.where(take, u, exit_step)
            remaining = remaining * (1.0 - lam)
        return x, kv, balance, remaining, cdf, selected, exit_step, ran

    carry = (x, kv_cache, moe_balance, jnp.ones((b, t), jnp.float32),
             jnp.zeros((b, t), jnp.float32), jnp.zeros_like(x),
             jnp.full((b, t), -1, jnp.int32), jnp.zeros((), jnp.int32))
    _, kv_cache, moe_balance, _, _, selected, exit_step, ran = \
        jax.lax.fori_loop(0, cfg.loop_steps, one_pass, carry)
    if not cfg.exit_gate:
        exit_step = jnp.full((b, t), last, jnp.int32)
    exit_info = jnp.stack([exit_step, jnp.broadcast_to(ran, (b, t))], -1)
    return selected.astype(compute_dtype), kv_cache, moe_balance, exit_info


def decoder_forward(params: Params, tokens: jnp.ndarray, cfg: DecoderConfig,
                    positions: Optional[jnp.ndarray] = None,
                    kv_cache: Optional[Params] = None,
                    cache_len: Optional[jnp.ndarray] = None,
                    decode: bool = False,
                    return_hidden: bool = False,
                    return_moe_aux: bool = False,
                    mesh=None, return_exit: bool = False,
                    n_valid: Optional[jnp.ndarray] = None,
                    return_moe_picks: bool = False):
    """Run the decoder.

    - train/eval: ``decoder_forward(params, tokens, cfg)`` → logits [B,T,V]
    - prefill:   pass ``kv_cache`` (positions default to arange) → (logits, cache)
    - decode:    ``decode=True`` with tokens [B,1], positions [B,1], cache_len [B]
                 → (logits [B,1,V], cache)
    - ``mesh``:  the serving mesh when params and cache are sharded over one
                 (``MeshPolicy.mesh``) — the attention kernels then run per
                 chip on its own heads
    - ``return_exit``: also return, last, int32 [B, T, 2]: the pass whose
                 state the head read at each position (a looped decoder's
                 exit gate; the last pass where there is no gate) and the
                 passes the device ran for it (1 for a plain decoder)
    - ``n_valid``: int32 [B]: how many of each row's tokens are real (a
                 padded chunk tail, an idle decode lane): a layer pattern's
                 KDA state is advanced by real tokens alone, and an expert
                 layer at few rows reads the experts the real tokens picked
    - ``return_moe_picks``: also return, last, the global ids of the experts
                 every token chose in every expert layer that says so, int32
                 [B, T, expert layers, top_k]: a layer pattern's always; a
                 plain expert decoder's where ``n_valid`` made its layers
                 read only the picked experts, else nothing is added
    """
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.dim ** 0.5, dtype=cfg.dtype)
        if cfg.embed_mult != 1.0:
            x = x * jnp.asarray(cfg.embed_mult, dtype=cfg.dtype)

    # a cache longer than the model's positions (``max_seq_len``) is a
    # configuration error: its far slots are positions the model was never
    # trained to hold — refuse the static-shape mismatch at trace time
    if kv_cache is not None:
        cache_s = kvstate.dense_len(kv_cache)
        if cache_s > cfg.max_seq_len:
            raise ValueError(
                f"kv cache length {cache_s} exceeds the model's "
                f"{cfg.max_seq_len} positions")
    # the angles of the rows this forward feeds, once: every layer and every
    # pass of a looped decoder rotates by the one pair
    sin = cos = None
    if cfg.rope:
        with jax.named_scope("attn.rope"):
            sin, cos = rope_rows(positions, cfg.mla_rope or cfg.head_dim,
                                 cfg.rope_theta, cfg.rope_yarn)

    moe_balance = jnp.zeros((), jnp.float32)
    exit_info = moe_picks = None
    if not cfg.looped:
        # a stream carried in float32 (``cfg.wide_stream``), every sub-layer
        # still computing in the embeddings' type, as in the pass loop
        # (``_looped_passes``); None: the stream's own type throughout
        compute_dtype = x.dtype if cfg.wide_stream else None
        if compute_dtype is not None:
            x = x.astype(jnp.float32)
        x, kv_cache, moe_balance, moe_picks = _layers(
            params, x, cfg, positions, sin, cos, kv_cache, 0, cache_len,
            decode, mesh, moe_balance, compute_dtype, n_valid)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_offset)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
    else:
        x, kv_cache, moe_balance, exit_info = _looped_passes(
            params, x, cfg, positions, sin, cos, kv_cache, cache_len,
            decode, mesh, moe_balance)
    if return_hidden:
        logits = None
    else:
        with jax.named_scope("head"):
            if cfg.tie_embeddings:
                logits = (x @ params["embed"].T.astype(cfg.dtype)).astype(
                    jnp.float32)
            else:
                logits = maybe_matmul(x, params["lm_head"]).astype(
                    jnp.float32)
            if cfg.logit_softcap > 0:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)
            if cfg.logit_div != 1.0:
                logits = logits / cfg.logit_div

    out = (x if return_hidden else logits,)
    if kv_cache is not None:
        out += (kv_cache,)
    if return_moe_aux:
        # mean balance loss across layers (training regularizer)
        out += (moe_balance / max(cfg.n_layers, 1),)
    if return_moe_picks and moe_picks is not None:
        out += (moe_picks,)
    if return_exit:
        out += (jnp.stack([jnp.zeros((b, t), jnp.int32),
                           jnp.ones((b, t), jnp.int32)], -1)
                if exit_info is None else exit_info,)
    return out if len(out) > 1 else out[0]


def count_params(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
