"""Engine construction from model presets, shared by the LLM runner and the
benchmark harness so the number the bench reports comes from the exact code
path a ``@endpoint`` deployment serves.

The flagship single-chip serving config is ``llama3-8b`` with int8
weight-only quantization: 8B params in bf16 are 16.06 GB — more than a
v5e's 16 GiB HBM — so the reference north-star config #2 (Llama-3-8B on
v5e-1, BASELINE.md) is served int8 (~8.1 GB weights + bf16 KV cache), the
standard weight-only recipe for this chip class.
"""

from __future__ import annotations

from typing import Optional

from .engine import EngineConfig, InferenceEngine


def resolve_preset(name: str, quantize: Optional[str] = None):
    """Return (DecoderConfig, quantized: bool) for a preset name.
    ``<preset>-int8`` suffixes select int8 weight-only quantization;
    ``quantize="int8"`` selects it for a plain name (the per-preset
    opt-in ``load_engine`` exposes — ISSUE 6)."""
    from ..ops.quant import validate_quant_mode
    quantize = validate_quant_mode(quantize)
    if quantize and quantize != "int8":
        # a mode added to SUPPORTED_MODES but not wired into the init/
        # quantizer path must fail here, not silently build a bf16 tree
        raise NotImplementedError(
            f"quantize mode {quantize!r} is not wired into the presets")
    from ..models.gemma import GEMMA_PRESETS
    from ..models.llama import LLAMA_PRESETS
    from ..models.mixtral import MIXTRAL_PRESETS
    from ..models.ouro import OURO_PRESETS
    presets = {**LLAMA_PRESETS, **GEMMA_PRESETS, **MIXTRAL_PRESETS,
               **OURO_PRESETS}
    quantized = name.endswith("-int8") or quantize == "int8"
    base = name[:-len("-int8")] if name.endswith("-int8") else name
    if base not in presets:
        raise KeyError(f"unknown model preset {base!r}; have {sorted(presets)}")
    return presets[base], quantized


def build_params(name: str, seed: int = 0, quantize: Optional[str] = None,
                 policy=None):
    """Random-initialized params for a preset (weight loading from a real
    checkpoint is ``tpu9.serving.weights``' concern). int8 presets are
    synthesized directly at int8 so the bf16 intermediate never exists.
    A mesh ``policy`` builds every leaf straight into its shard — a model
    sharded BECAUSE it fits no single chip (llama3-8b bf16 on v5e-4) must
    never be materialized on the first one."""
    import jax
    cfg, quantized = resolve_preset(name, quantize)
    return init_params(cfg, quantized, jax.random.PRNGKey(seed), policy), cfg


def init_params(cfg, quantized: bool, rng, policy=None):
    """:func:`build_params` for an explicit DecoderConfig."""
    if quantized:
        from ..ops.quant import init_quantized_decoder as init
    else:
        from ..models import init_decoder as init
    if policy is None:
        return init(rng, cfg)
    return policy.build_params(lambda r: init(r, cfg), rng)


def abstract_params_for(cfg, quantized: bool = False):
    """Abstract (``jax.ShapeDtypeStruct``) param tree for an explicit
    DecoderConfig — ``jax.eval_shape`` over the same init fn real params
    come from, so spec and params can never drift apart. Exposed for
    graphcheck's depth-reduced matrix cells (ISSUE 11); presets go
    through :func:`params_spec`."""
    import jax
    if quantized:
        from ..ops.quant import init_quantized_decoder
        init = init_quantized_decoder
    else:
        from ..models import init_decoder
        init = init_decoder
    return jax.eval_shape(lambda rng: init(rng, cfg), jax.random.PRNGKey(0))


def params_spec(name: str, quantize: Optional[str] = None):
    """Abstract (``jax.ShapeDtypeStruct``) param tree for a preset — the
    shapes compile-ahead needs before a single weight byte has streamed."""
    cfg, quantized = resolve_preset(name, quantize)
    return abstract_params_for(cfg, quantized), cfg


def load_engine(name: str, *, max_batch: int = 8, max_seq_len: int = 2048,
                prefill_buckets: tuple = (128, 512, 2048),
                decode_steps: tuple = (1, 8, 32),
                paged: Optional[bool] = None,
                kv_block_size: int = 256,
                kv_pool_blocks: int = 0,
                prefix_cache_blocks: Optional[int] = None,
                spec_len: int = 0,
                spec_min_accept: float = 0.35,
                quantize: Optional[str] = None,
                kv_quant: Optional[str] = None,
                flight_cap: int = 256,
                engine_cfg: Optional[EngineConfig] = None,
                seed: int = 0,
                compile_ahead: bool = False,
                topology=None,
                tpu: Optional[str] = None) -> InferenceEngine:
    """``paged=None`` (default) enables the paged-KV engine whenever the
    alignment invariants hold (block | chunk | max_seq_len) — the
    production serving path (block allocator + chunked prefill + prefix
    reuse). ``paged=False`` forces the legacy dense cache.
    ``prefix_cache_blocks=0`` DISABLES the prefix cache (None = auto).

    ``spec_len`` enables self-speculative decoding (prompt-lookup n-gram
    drafts verified in one batched forward — ISSUE 5): no draft model, so
    it works for EVERY preset; ``spec_min_accept`` is the acceptance-EWMA
    floor below which the engine auto-falls-back to classic windowed
    decode (adversarial prompts never regress past a probe's worth of
    wasted verify compute). Greedy output is token-identical with the
    knob on or off.

    ``quantize="int8"`` opts a PLAIN preset name into int8 weight-only
    serving (equivalent to the ``-int8`` suffix); ``kv_quant="int8"``
    stores the paged KV pool as int8 with per-vector scales, sizing the
    auto pool to the same HBM the bf16 pool would use — ~2x the blocks,
    so admission headroom and the router's heartbeated ``kv_blocks``
    double (ISSUE 6). The two knobs are independent; together they are
    quantized serving end-to-end.

    ``compile_ahead=True`` builds the engine on the preset's ABSTRACT param
    spec and runs :meth:`InferenceEngine.precompile` in a thread WHILE the
    weights materialize, binding them when both finish — serving bring-up
    pays max(compile, weight load) instead of their sum (λScale-style
    pipelined bring-up; the per-graph timings land in
    ``engine.compile_ahead_timings``).

    ``topology`` (ISSUE 9) selects the serving submesh: ``"2x1"`` /
    ``"tp=2,fsdp=2"`` / a :class:`~tpu9.serving.shard.Topology` shard
    weights and the paged-KV head axis across tp(×fsdp) local devices;
    ``"auto"`` plans the smallest submesh that provably fits (needs
    ``tpu``, e.g. ``"v5e-8"``, for the HBM arithmetic). ``None`` honors
    the ``TPU9_TOPOLOGY`` env override and otherwise serves single-chip —
    a ``1x1`` engine compiles bit-identical graphs to a topology-oblivious
    build."""
    cfg, _quantized = resolve_preset(name, quantize)
    from .shard import make_policy, resolve_topology
    topo = resolve_topology(topology, preset=name, tpu=tpu,
                            max_batch=max_batch, max_seq_len=max_seq_len,
                            quantize=quantize, kv_quant=bool(kv_quant),
                            kv_pool_blocks=kv_pool_blocks,
                            kv_block_size=min(kv_block_size,
                                              min(prefill_buckets)))
    policy = make_policy(topo)
    from ..ops.quant import validate_quant_mode
    kv_quant = validate_quant_mode(kv_quant, "kv_quant")
    if engine_cfg is not None and kv_quant \
            and engine_cfg.kv_quant != kv_quant:
        # an explicit engine_cfg replaces the whole knob surface — a
        # kv_quant opt-in it doesn't carry would be silently dropped,
        # serving a bf16 pool the caller sized admission/HBM around
        raise ValueError(
            "kv_quant conflicts with the explicit engine_cfg — set "
            "EngineConfig(kv_quant=...) there instead")
    # the chunk is the smallest prefill bucket; the block size must divide
    # it (a chunk smaller than a block would lose prefill KV — the engine
    # rejects that) AND divide max_seq_len; max_seq_len must also be a
    # chunk multiple or the final chunk window would clamp past the cache
    chunk = min(prefill_buckets)
    block = min(kv_block_size, chunk)
    if paged is None:
        paged = (max_seq_len % block == 0 and chunk % block == 0
                 and max_seq_len % chunk == 0)
    if kv_quant and not paged:
        # silently serving a bf16 pool after an explicit int8-KV opt-in
        # would fake the capacity win the caller sized admission around
        raise ValueError(
            "kv_quant='int8' needs the paged engine, but the alignment "
            f"invariants rejected paging (block {block}, chunk {chunk}, "
            f"max_seq_len {max_seq_len})")
    ecfg = engine_cfg or EngineConfig(
        max_batch=max_batch, max_seq_len=max_seq_len,
        prefill_buckets=prefill_buckets, decode_steps=decode_steps,
        kv_block_size=block if paged else 0,
        kv_pool_blocks=kv_pool_blocks,
        prefill_chunk=chunk if paged else 0,
        # `or` would make an explicit 0 (documented: disables) silently
        # re-enable the auto default
        prefix_cache_blocks=prefix_cache_blocks
        if prefix_cache_blocks is not None
        else (max_seq_len // block if paged else 0),
        spec_len=spec_len, spec_min_accept=spec_min_accept,
        kv_quant=kv_quant or "",
        # flight recorder (ISSUE 8): per-window black box; 0 disables
        flight_cap=flight_cap)
    if compile_ahead:
        import logging
        import threading
        import time

        from ..observability import coldstart as _cs
        from ..observability.trace import tracer
        from ..utils import on_tpu
        spec, _ = params_spec(name, quantize)
        engine = InferenceEngine(spec, cfg, ecfg, policy=policy)
        timings: dict = {}
        errors: list = []
        # monotonic window of the ACTUAL compile work inside the thread,
        # recorded as a restore.compile_ahead span after join — the
        # overlap with the weight-load interval is the evidence that
        # bring-up paid max(compile, load), not their sum (ISSUE 13)
        compile_iv: list = [None, None]

        def _precompile() -> None:
            compile_iv[0] = time.monotonic()
            try:
                timings.update(engine.precompile())
            except Exception as exc:   # noqa: BLE001 — surfaced after join
                errors.append(exc)
            finally:
                compile_iv[1] = time.monotonic()

        wall_anchor = time.time()
        anchor_mono = time.monotonic()
        compiler = threading.Thread(target=_precompile,
                                    name="tpu9-compile-ahead", daemon=True)
        compiler.start()
        params, _ = build_params(name, seed=seed,    # ∥ the compile
                                 quantize=quantize, policy=policy)
        load_end = time.monotonic()
        compiler.join()
        if errors:
            if on_tpu():
                # lazy recompilation would meet the same compiler error at
                # the first request, later and less legibly
                raise RuntimeError(
                    f"compile-ahead of {name!r} failed") from errors[0]
            # lazy compile still serves correctly — but the bring-up stall
            # compile-ahead exists to hide must be attributable in logs
            logging.getLogger("tpu9.serving").warning(
                "compile-ahead failed (%s); graphs compile lazily on "
                "first use", errors[0])
        tracer.record_window(_cs.SPAN_LOAD, wall_anchor, anchor_mono,
                             anchor_mono, load_end,
                             attrs={"preset": name, "source": "build"})
        tracer.record_window(_cs.SPAN_COMPILE_AHEAD, wall_anchor,
                             anchor_mono, compile_iv[0], compile_iv[1],
                             attrs={"preset": name,
                                    "graphs": len(timings),
                                    "failed": bool(errors)})
        bind_start = time.monotonic()
        with tracer.span(_cs.SPAN_BIND, attrs={"preset": name}):
            engine.bind_params(params)
        bind_end = time.monotonic()
        engine.compile_ahead_timings = timings
        # bring-up decomposition the runner heartbeats as coldstart_*
        # extras (flat scalars; engine.stats() forwards them verbatim)
        engine.bringup = {
            "load_s": round(load_end - anchor_mono, 4),
            "compile_ahead_s": round((compile_iv[1] or anchor_mono)
                                     - (compile_iv[0] or anchor_mono), 4),
            "bind_s": round(bind_end - bind_start, 4),
            "compile_overlap_s": round(_cs.interval_overlap_s(
                (anchor_mono, load_end),
                (compile_iv[0], compile_iv[1])), 4),
            # per-graph compile_<graph>_s, so a slow bring-up names its graph
            **timings}
        return engine
    params, _ = build_params(name, seed=seed, quantize=quantize,
                             policy=policy)
    # placement through the policy BEFORE construction: the engine's pool
    # arrays and the weights must land on the same submesh
    return InferenceEngine(policy.place_params(params), cfg, ecfg,
                           policy=policy)
