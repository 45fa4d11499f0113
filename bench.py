#!/usr/bin/env python3
"""tpu9 benchmark — prints ONE JSON line.

Every number in the line is defended by evidence computed in-harness
(`tpu9/benchsuite/physics.py`), the same evidence-or-fail stance as the
reference's b9bench validators (`benchmarks/b9bench/validators.py:6-60`):

- **Fencing**: all timing windows end in a forced device→host copy of data
  computed by the window (``np.asarray(jax.device_get(...))``), so a window
  can never time the enqueue instead of the work.
- **Physics**: model-bandwidth-utilization and MFU are computed for every
  throughput phase and the phase FAILS if either is >= 1.0 — a number that
  implies more than HBM bandwidth or MXU peak is a timing bug, not a result.
- **Linear scaling**: doubling the decode-step count must ~double elapsed
  time, which catches async backends whose clock stops early.
- **Engine path**: the headline LLM number comes from the serving
  InferenceEngine (and, on TPU, through a real ``@endpoint`` deployment of
  the LLM runner), not a hand-rolled loop.

Phases (each in a fresh subprocess so they cannot interfere, and so only one
process at a time holds the chip):

1. **llm**: Llama3-8B int8 weight-only (bf16 8B = 16.06 GB does not fit a
   v5e's 16 GiB HBM; int8 is the standard single-chip recipe) — raw decode
   windows through the engine's own compiled graph, then the engine
   end-to-end with concurrent requests.
2. **llm_endpoint** (TPU only): same engine served by ``tpu9.runner.llm``
   behind ``@endpoint tpu=v5e-1`` through the real gateway/scheduler/worker
   stack; reports served tokens/sec with a container-side served-count proof.
3. **kernels**: pallas flash-attention + ragged paged-decode vs the XLA
   fallback — correctness (max abs diff) + fenced latency + MFU sanity.
4. **coldstart**: deploy→first-response p50 through the real local stack
   (gateway + scheduler + worker + subprocess runner), forced CPU.

Primary metric: cold_start_p50_s with ``vs_baseline`` = 1.0 / p50 against
the reference's headline "under a second" cold-start claim (README.md:39 of
beam-cloud/beta9). LLM throughput + kernel numbers + their evidence ride in
``extra``; any number whose evidence fails is REMOVED from extra and
replaced by a ``*_rejected`` reason.

Usage:
    python3 bench.py [--quick] [--cpu]               # full orchestrated run
    python3 bench.py --phase llm|llm_endpoint|kernels|coldstart
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import struct
import subprocess
import sys
import time

from tpu9.utils import compile_cache_dir
from tpu9.utils.aio import cancellable_wait

PHASE_TIMEOUT_S = {"llm": 1800, "llm_endpoint": 1800, "kernels": 900,
                   "coldstart": 900, "coldstart_native": 900,
                   "coldstart_jax": 900, "coldstart_jax_tpu": 900,
                   "coldstart_stream": 900, "router": 300, "spec": 900,
                   "quant": 900, "obs": 900, "multichip": 900,
                   "faults": 300, "disagg": 600, "scaleout": 600,
                   "kvtier": 600}

# one compile cache for every phase and every runner container (identical
# graphs → the endpoint phase skips the 8B compiles the llm phase paid)
XLA_CACHE_DIR = compile_cache_dir()


def _cold_cache_dir(name: str) -> str:
    """A fixed, EMPTIED sub-directory of the compile cache for a restore
    phase that must start cold — never a fresh temp path (the path is part
    of the cache key)."""
    import shutil
    path = os.path.join(XLA_CACHE_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def fence(x) -> float:
    """Force completion of x's computation by copying a small dependent
    slice to host. Returns a checksum so callers can accumulate it (keeps
    the compiler from eliminating the work)."""
    import jax
    import numpy as np
    leaf = jax.tree_util.tree_leaves(x)[0]
    host = np.asarray(jax.device_get(leaf.ravel()[:8].astype("float32")))
    return float(host.sum())


def _known_chip(device_kind: str):
    """The device's peak figures, or None on a device the peak table does
    not know — a CPU functional run. Its phases then write no utilization:
    there is no peak to price one against."""
    from tpu9.benchsuite.physics import chip_spec
    try:
        return chip_spec(device_kind)
    except KeyError:
        return None


# ---------------------------------------------------------------------------
# phase: llm decode throughput (engine graph + engine e2e)
# ---------------------------------------------------------------------------

def _llm_settings(tpu: bool, quick: bool) -> dict:
    if quick or not tpu:
        return dict(preset="llama-tiny", batch=4, max_seq=256, ctx0=64,
                    window_k=8, windows=2, prefill_buckets=(32, 64),
                    decode_steps=(1, 4, 8), requests=4, max_new=13,
                    prompt_len=24)
    # requests == max_batch: with work queued the engine drops to K=1
    # admission-latency windows — steady-state throughput is all slots busy
    # with no queue, decoding K=32 windows
    return dict(preset="llama3-8b-int8", batch=8, max_seq=2048, ctx0=512,
                window_k=32, windows=4, prefill_buckets=(128,),
                decode_steps=(1, 8, 32), requests=8, max_new=41,
                prompt_len=120)


def bench_llm(quick: bool = False) -> dict:
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu9.benchsuite.physics import (decode_byte_counts,
                                         decode_physics,
                                         linear_scaling_violations,
                                         physics_violations)
    from tpu9.serving.presets import load_engine
    from tpu9.utils import on_tpu

    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", XLA_CACHE_DIR)

    tpu = on_tpu()
    s = _llm_settings(tpu, quick)
    dev = jax.devices()[0]
    spec = _known_chip(dev.device_kind)
    out: dict = {
        "backend": jax.default_backend(), "on_tpu": tpu,
        "device_kind": dev.device_kind,
        "chip_spec": {"name": spec.name, "hbm_gbps": spec.hbm_gbps,
                      "peak_bf16_tflops": spec.peak_bf16_tflops}
        if spec else None,
        "model": s["preset"], "batch": s["batch"],
        "max_seq_len": s["max_seq"],
        "note": ("llama3-8b served int8 weight-only: 8B bf16 = 16.06 GB > "
                 "16 GiB v5e HBM" if "8b" in s["preset"] else ""),
    }
    violations: list[str] = []

    t0 = time.perf_counter()
    engine = load_engine(s["preset"], max_batch=s["batch"],
                         max_seq_len=s["max_seq"],
                         prefill_buckets=s["prefill_buckets"],
                         decode_steps=s["decode_steps"])
    fence(engine.params["layers"][0]["wq"])
    out["param_init_s"] = round(time.perf_counter() - t0, 2)

    counts = decode_byte_counts(engine.params, engine.cfg, s["batch"],
                                s["ctx0"])
    out["streamed_weight_gb"] = round(counts["streamed_bytes"] / 1e9, 3)

    # --- raw decode windows through the ENGINE's compiled decode graph ----
    k = s["window_k"]
    if getattr(engine, "paged", False):
        # paged engine: slots need real physical blocks so the decode
        # windows move production-shaped HBM traffic
        engine.bench_reset_slots(
            s["ctx0"], 3 * s["windows"] * s["window_k"] + max(
                s["decode_steps"]))
        out["kv_mode"] = (f"paged(block={engine.ecfg.kv_block_size}, "
                          f"pool={engine.allocator.n_blocks})")
    dec = engine._decode_k(k)
    cache_len = jnp.full((s["batch"],), s["ctx0"], jnp.int32)
    last = jnp.ones((s["batch"], 1), jnp.int32)
    active = jnp.ones((s["batch"],), bool)
    rng = jax.random.PRNGKey(0)
    kv = engine.kv_cache

    t0 = time.perf_counter()
    last, kv, cache_len, rng, toks = dec(engine.params, kv, last, cache_len,
                                         active, rng)
    checksum = fence(toks)
    out["decode_compile_s"] = round(time.perf_counter() - t0, 2)

    def run_windows(n: int) -> float:
        nonlocal last, kv, cache_len, rng, checksum
        # reset position so every run does identical work
        cache_len = jnp.full((s["batch"],), s["ctx0"], jnp.int32)
        checksum += fence(cache_len)                      # start fence
        t0 = time.perf_counter()
        for _ in range(n):
            last, kv, cache_len, rng, toks = dec(
                engine.params, kv, last, cache_len, active, rng)
            checksum += fence(toks)                       # window fence
        return time.perf_counter() - t0

    w = s["windows"]
    elapsed_1x = run_windows(w)
    elapsed_2x = run_windows(2 * w)
    # the raw loop donated the engine's cache through each call — hand the
    # final buffer back so the engine e2e below starts from a live cache
    engine.kv_cache = kv
    out["fence_checksum"] = round(checksum, 2)
    out["raw_elapsed_1x_s"] = round(elapsed_1x, 4)
    out["raw_elapsed_2x_s"] = round(elapsed_2x, 4)
    out["raw_scaling_ratio"] = round(elapsed_2x / max(elapsed_1x, 1e-9), 3)

    steps = w * k
    step_ms = elapsed_1x / steps * 1e3
    raw_tps = s["batch"] * steps / elapsed_1x
    phys = decode_physics(
        step_ms=step_ms, batch=s["batch"],
        streamed_bytes=counts["streamed_bytes"],
        kv_bytes_per_step=counts["kv_bytes_per_step"],
        matmul_params=counts["matmul_params"],
        attn_flops_per_step=counts["attn_flops_per_step"],
        spec=spec) if spec else {}
    out["raw_decode_step_ms"] = round(step_ms, 3)
    out["raw_decode_tokens_per_sec"] = round(raw_tps, 1)
    out["raw_physics"] = phys

    if tpu:
        violations += physics_violations(phys, what="raw decode")
        violations += linear_scaling_violations(
            elapsed_1x, elapsed_2x, what="raw decode")

    # --- engine end-to-end: concurrent requests through generate() --------
    async def engine_e2e() -> dict:
        t0 = time.perf_counter()
        engine.warmup()        # compile all prefill/decode graphs up front
        await engine.start()
        prompt = list(range(3, 3 + s["prompt_len"]))
        await engine.generate(prompt, max_new_tokens=s["max_new"])
        warm_s = time.perf_counter() - t0
        before = engine._stats["tokens_generated"] + 1    # + prefill token
        t0 = time.perf_counter()
        results = await asyncio.gather(*[
            engine.generate([p + i for p in prompt],
                            max_new_tokens=s["max_new"])
            for i in range(s["requests"])])
        elapsed = time.perf_counter() - t0
        await engine.stop()
        total = sum(len(r) for r in results)
        # served proof: the engine's own counter must account for every
        # token the callers received (first tokens come from prefill and are
        # not in tokens_generated — count them explicitly)
        counted = (engine._stats["tokens_generated"] + len(results)
                   + 1) - before
        return {"warm_s": warm_s, "elapsed": elapsed, "total": total,
                "counted": counted}

    ee = asyncio.run(engine_e2e())
    out["engine_warmup_s"] = round(ee["warm_s"], 2)
    out["engine_requests"] = s["requests"]
    out["engine_tokens_returned"] = ee["total"]
    out["engine_elapsed_s"] = round(ee["elapsed"], 3)
    engine_tps = ee["total"] / ee["elapsed"]
    out["engine_tokens_per_sec"] = round(engine_tps, 1)
    out["engine_tokens_per_sec_per_chip"] = round(engine_tps, 1)
    out["engine_served_proof_ok"] = ee["counted"] >= ee["total"]
    if not out["engine_served_proof_ok"]:
        violations.append(
            f"engine: callers received {ee['total']} tokens but engine "
            f"counted {ee['counted']}")

    # engine-path physics: requests run in waves of max_batch; per-step
    # weight bytes are the same as raw decode (weights stream regardless
    # of occupancy), but the KV/attention terms use the E2E workload's own
    # mean context (prompt + half the generation budget) — the raw loop's
    # ctx0 would overstate KV traffic and fake the ceiling ratio (ISSUE 5
    # satellite: engine_mbu/mfu must be honest, not copied from another
    # workload's accounting)
    eng_counts = decode_byte_counts(
        engine.params, engine.cfg, s["batch"],
        s["prompt_len"] + s["max_new"] // 2)
    eng_steps = ee["total"] / s["batch"]                  # lower bound
    eng_step_ms = ee["elapsed"] / max(eng_steps, 1e-9) * 1e3
    eng_phys = decode_physics(
        step_ms=eng_step_ms, batch=s["batch"],
        streamed_bytes=eng_counts["streamed_bytes"],
        kv_bytes_per_step=eng_counts["kv_bytes_per_step"],
        matmul_params=eng_counts["matmul_params"],
        attn_flops_per_step=eng_counts["attn_flops_per_step"],
        spec=spec) if spec else {}
    out["engine_physics"] = eng_phys
    if tpu:
        violations += physics_violations(eng_phys, what="engine decode")

    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: llm through a real @endpoint deployment (runner container on TPU)
# ---------------------------------------------------------------------------

LLM_BENCH_APP = """
from tpu9.serving.presets import load_engine

def load():
    return load_engine("{preset}", max_batch={batch}, max_seq_len={max_seq},
                       prefill_buckets={prefill_buckets},
                       decode_steps={decode_steps})
"""


def bench_llm_endpoint(quick: bool = False) -> dict:
    """Serve the flagship engine behind ``@endpoint tpu=v5e-1`` through the
    real gateway/scheduler/worker stack. The gateway/worker stay forced-CPU
    (this process must be the first and only thing here to touch jax, and it
    pins the CPU); the runner container gets the chip from the worker's
    assignment, reports the device it landed on through ``/health``, and the
    phase fails unless that is the TPU it asked for."""
    import asyncio

    want_tpu = not quick and os.environ.get("TPU9_BENCH_CPU") != "1"

    from tpu9.utils import force_cpu
    force_cpu(host_devices=0)      # this process must never hold the chip

    from tpu9.testing.localstack import LocalStack

    s = _llm_settings(want_tpu, quick)

    app = LLM_BENCH_APP.format(
        preset=s["preset"], batch=s["batch"], max_seq=s["max_seq"],
        prefill_buckets=tuple(s["prefill_buckets"]),
        decode_steps=tuple(s["decode_steps"]))

    async def run() -> dict:
        out: dict = {"endpoint_model": s["preset"]}
        violations: list[str] = []
        async with LocalStack(pool_tpu_type="v5e-1") as stack:
            # asked for the chip: the worker inventories the host's real
            # device nodes; a CPU run gets one fake chip (JAX on the CPU)
            worker = await stack._worker_factory(
                tpu_chips=0 if want_tpu else 1, tpu_generation="v5e")
            if not worker.tpu.chip_count:
                return {"llm_endpoint_error":
                        "asked for a TPU; the worker found no chip"}
            dep = await stack.deploy_endpoint(
                "llm-bench", {"app.py": app}, "app:load",
                config_extra={
                    "timeout_s": 1500.0,
                    "concurrent_requests": 64,
                    "extra": {"runner": "llm"},
                    "runtime": {"tpu": "v5e-1", "cpu_millicores": 2000,
                                "memory_mb": 16384},
                    "autoscaler": {"max_containers": 1}})
            prompt = list(range(3, 3 + s["prompt_len"]))
            t0 = time.perf_counter()
            status, warm = await stack.api(
                "POST", "/endpoint/llm-bench",
                json_body={"tokens": prompt, "max_new_tokens": s["max_new"]},
                timeout=1500)
            out["endpoint_warmup_s"] = round(time.perf_counter() - t0, 2)
            if status != 200:
                return {"llm_endpoint_error": f"warmup status {status}: "
                        f"{str(warm)[:300]}"}
            # pre-run served counter: the proof below must cover ONLY the
            # timed requests, not the warmup's tokens
            status, h0 = await stack.api("GET", "/endpoint/llm-bench/health")
            served_before = int(h0.get("tokens_generated", 0)) \
                if status == 200 else -1
            platform = h0.get("device_platform", "") if status == 200 else ""
            out["endpoint_device"] = {
                "platform": platform, "kind": h0.get("device_kind", ""),
                "count": h0.get("device_count", 0)}
            on_real_tpu = platform == "tpu"
            out["endpoint_container_on_tpu"] = on_real_tpu
            if want_tpu and not on_real_tpu:
                return {"llm_endpoint_error":
                        f"asked for a TPU, runner reports {platform!r}"}

            async def one(i: int):
                return await stack.api(
                    "POST", "/endpoint/llm-bench",
                    json_body={"tokens": [p + i for p in prompt],
                               "max_new_tokens": s["max_new"]},
                    timeout=1500)

            t0 = time.perf_counter()
            results = await asyncio.gather(*[one(i)
                                             for i in range(s["requests"])])
            elapsed = time.perf_counter() - t0
            bad = [r for r in results if r[0] != 200]
            if bad:
                return {"llm_endpoint_error":
                        f"{len(bad)} failed requests: {str(bad[0])[:300]}"}
            total = sum(len(r[1]["tokens"]) for r in results)

            # container-side served proof via the runner's /health stats:
            # decode-counter delta + one prefill-sampled token per request
            status, health = await stack.api("GET",
                                             "/endpoint/llm-bench/health")
            served = (int(health.get("tokens_generated", 0)) - served_before
                      + len(results)) if status == 200 and served_before >= 0 \
                else -1
            out["endpoint_requests"] = s["requests"]
            out["endpoint_tokens_returned"] = total
            out["endpoint_elapsed_s"] = round(elapsed, 3)
            tps = total / elapsed
            out["endpoint_tokens_per_sec"] = round(tps, 1)
            out["endpoint_tokens_per_sec_per_chip"] = round(tps, 1)
            out["endpoint_served_proof_ok"] = served >= total
            if not out["endpoint_served_proof_ok"]:
                violations.append(
                    f"endpoint: received {total} tokens but container "
                    f"reports {served}")

            if on_real_tpu:
                from tpu9.benchsuite.physics import (chip_spec,
                                                     decode_physics,
                                                     physics_violations)
                from tpu9.serving.presets import resolve_preset
                cfg, _ = resolve_preset(s["preset"])
                # weight bytes from config (the engine lives in the
                # container; recompute analytically at int8 widths)
                per_layer = (cfg.dim * cfg.n_heads * cfg.head_dim
                             + 2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim
                             + cfg.n_heads * cfg.head_dim * cfg.dim
                             + 3 * cfg.dim * cfg.hidden_dim)
                matmul_params = (per_layer * cfg.n_layers
                                 + cfg.dim * cfg.vocab_size)
                streamed = matmul_params          # int8: 1 byte/param
                kv_row = cfg.n_kv_heads * cfg.head_dim * 2
                kv_bytes = 2 * cfg.n_layers * s["batch"] * (
                    s["prompt_len"] + s["max_new"] // 2) * kv_row
                eng_step_ms = elapsed / max(total / s["batch"], 1e-9) * 1e3
                spec = chip_spec(out["endpoint_device"]["kind"])
                phys = decode_physics(
                    step_ms=eng_step_ms, batch=s["batch"],
                    streamed_bytes=streamed, kv_bytes_per_step=kv_bytes,
                    matmul_params=matmul_params, spec=spec)
                out["endpoint_physics"] = phys
                violations += physics_violations(phys, what="endpoint decode")
        out["violations"] = violations
        out["valid"] = not violations
        return out

    return asyncio.run(run())


# ---------------------------------------------------------------------------
# phase: kernel validation (pallas vs XLA: correctness + fenced step time)
# ---------------------------------------------------------------------------

def bench_kernels(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu9.benchsuite.physics import matmul_physics, physics_violations
    from tpu9.ops.attention import flash_attention, xla_attention
    from tpu9.ops.paged_attention import ragged_decode_attention
    from tpu9.utils import on_tpu

    tpu = on_tpu()
    interpret = not tpu           # CPU runs the same kernels interpreted
    dev = jax.devices()[0]
    spec = _known_chip(dev.device_kind)
    out: dict = {"backend": jax.default_backend(), "on_tpu": tpu}
    violations: list[str] = []

    def timeit(fn, *args, iters=3 if quick or not tpu else 20, **kw):
        r = fn(*args, **kw)
        fence(r)                                  # compile + warmup fence
        fence(args[0])                            # start fence
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args, **kw)
        fence(r)                                  # same-stream order: forces all
        return r, (time.perf_counter() - t0) / iters * 1000

    # flash attention: [B, T, H, D]
    b, t, h, d = (1, 256, 4, 64) if quick or not tpu else (4, 2048, 16, 128)
    kq = jax.random.PRNGKey(0)
    q = jax.random.normal(kq, (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, d), jnp.bfloat16)

    flash, flash_ms = timeit(flash_attention, q, k, v, causal=True,
                             interpret=interpret)
    ref, xla_ms = timeit(xla_attention, q, k, v, causal=True)
    out["flash_max_abs_diff"] = float(
        jnp.max(jnp.abs(flash.astype(jnp.float32) - ref.astype(jnp.float32))))
    out["flash_ms"] = round(flash_ms, 3)
    out["flash_xla_ms"] = round(xla_ms, 3)
    out["flash_shape"] = [b, t, h, d]
    # causal attention: ~0.5 * 4 * B*T^2*H*D FLOPs (half the square masked)
    flash_flops = 2.0 * b * t * t * h * d
    flash_bytes = 4 * b * t * h * d * 2           # q,k,v read + out write, bf16
    fp = matmul_physics(elapsed_ms=flash_ms, flops=flash_flops,
                        bytes_moved=flash_bytes, spec=spec) if spec else {}
    out["flash_physics"] = fp
    if tpu:
        violations += physics_violations(fp, what="flash attention")

    # ragged paged decode: q [B,1,QH,D], cache [B,S,KH,D]
    b, s, qh, kh, d = (2, 512, 8, 2, 64) if quick or not tpu \
        else (8, 4096, 16, 4, 128)
    q1 = jax.random.normal(kq, (b, 1, qh, d), jnp.bfloat16)
    kc = jax.random.normal(jax.random.PRNGKey(3), (b, s, kh, d), jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(4), (b, s, kh, d), jnp.bfloat16)
    lens = jnp.linspace(s // 4, s, b).astype(jnp.int32)

    paged, paged_ms = timeit(ragged_decode_attention, q1, kc, vc, lens,
                             interpret=interpret)
    from tpu9.ops.attention import xla_decode_attention
    ref2, xla2_ms = timeit(jax.jit(xla_decode_attention), q1, kc, vc, lens)
    out["paged_max_abs_diff"] = float(
        jnp.max(jnp.abs(paged.astype(jnp.float32) - ref2.astype(jnp.float32))))
    out["paged_ms"] = round(paged_ms, 3)
    out["paged_xla_ms"] = round(xla2_ms, 3)
    out["paged_shape"] = [b, s, qh, kh, d]

    # decode attention is bandwidth-bound: reads mean(lens) K+V rows/seq
    mean_len = float(jnp.mean(lens))
    paged_bytes = int(2 * b * mean_len * kh * d * 2)
    paged_flops = 4.0 * b * mean_len * qh * d
    pp = matmul_physics(elapsed_ms=paged_ms, flops=paged_flops,
                        bytes_moved=paged_bytes,
                        spec=spec) if spec else {}
    out["paged_physics"] = pp
    if tpu:
        violations += physics_violations(pp, what="paged decode")

    # block-table paged kernel (the serving engine's production read path):
    # same workload through a scrambled block POOL — correctness against
    # the densify+XLA oracle and fenced latency vs the dense ragged kernel
    from tpu9.ops.paged_attention import (paged_decode_attention,
                                          xla_paged_decode_attention)
    bs_blk = 128 if (quick or not tpu) else 256
    mb = s // bs_blk
    n_pool = b * mb + 4
    rng_t = np.random.default_rng(5)
    table_np = rng_t.permutation(n_pool)[:b * mb].reshape(b, mb)
    table = jnp.asarray(table_np, jnp.int32)
    pool_k = jnp.zeros((n_pool, bs_blk, kh, d), jnp.bfloat16)
    pool_v = jnp.zeros((n_pool, bs_blk, kh, d), jnp.bfloat16)
    kc_blocks = kc.reshape(b * mb, bs_blk, kh, d)
    vc_blocks = vc.reshape(b * mb, bs_blk, kh, d)
    pool_k = pool_k.at[table.reshape(-1)].set(kc_blocks)
    pool_v = pool_v.at[table.reshape(-1)].set(vc_blocks)

    blocktab, blocktab_ms = timeit(paged_decode_attention, q1, pool_k,
                                   pool_v, table, lens, interpret=interpret)
    oracle = xla_paged_decode_attention(q1, pool_k, pool_v, table, lens)
    out["blocktable_max_abs_diff"] = float(jnp.max(jnp.abs(
        blocktab.astype(jnp.float32) - oracle.astype(jnp.float32))))
    out["blocktable_ms"] = round(blocktab_ms, 3)
    out["blocktable_block_size"] = bs_blk
    bt = matmul_physics(elapsed_ms=blocktab_ms, flops=paged_flops,
                        bytes_moved=paged_bytes,
                        spec=spec) if spec else {}
    out["blocktable_physics"] = bt
    if tpu:
        violations += physics_violations(bt, what="block-table decode")
    # the oracle-diff check is backend-independent: a wrong kernel must be
    # rejected on the interpret path too, not just on-chip
    if out["blocktable_max_abs_diff"] > 0.05:
        violations.append(
            f"block-table kernel diverges from oracle by "
            f"{out['blocktable_max_abs_diff']}")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: serving cold start
# ---------------------------------------------------------------------------

def bench_cold_start(quick: bool = False) -> dict:
    """Deploy→first-response p50/p95/max through the local stack."""
    import asyncio

    from tpu9.testing.localstack import LocalStack  # noqa: WPS433

    trials = 5 if quick else 20

    async def run() -> dict:
        times = []
        backoffs = 0
        async with LocalStack() as stack:
            name = "bench-echo"
            deploy = await stack.deploy_echo_endpoint(name)
            # prime once so the first measured trial isn't paying one-time
            # stack setup (workspace unpack cache etc.)
            await stack.invoke(deploy, {"warm": 1})
            for _ in range(trials):
                await stack.scale_to_zero(deploy)
                t0 = time.perf_counter()
                resp = await stack.invoke(deploy, {"ping": 1})
                assert resp is not None
                times.append(time.perf_counter() - t0)
            inst = stack.gateway.endpoints.instances.get(deploy["stub_id"])
            if inst is not None:
                backoffs = getattr(inst.instance, "backoff_events", 0)
        times.sort()
        # nearest-rank p95: ceil(0.95*n)-th sample — for small n this is the
        # max, never an optimistic lower percentile mislabeled as p95
        p95_idx = max(0, -(-95 * len(times) // 100) - 1)
        out = {
            "cold_start_p50_s": round(statistics.median(times), 4),
            "cold_start_p95_s": round(times[p95_idx], 4),
            "cold_start_min_s": round(times[0], 4),
            "cold_start_max_s": round(times[-1], 4),
            "cold_start_backoff_events": backoffs,
            "trials": trials,
        }
        out["violations"] = (
            [f"coldstart: {backoffs} circuit-breaker backoff events "
             f"polluted the run"] if backoffs else [])
        out["valid"] = not out["violations"]
        return out

    return asyncio.run(run())


def _percentiles(times: list[float]) -> dict:
    times = sorted(times)
    p95_idx = max(0, -(-95 * len(times) // 100) - 1)
    return {"p50": round(statistics.median(times), 4),
            "p95": round(times[p95_idx], 4),
            "min": round(times[0], 4), "max": round(times[-1], 4)}


def _phase_report() -> dict:
    """p50/p95/max per lifecycle phase from the worker's startup timeline
    (reference: benchmarks/sandbox_startup_report.py — per-phase report
    derived from lifecycle events)."""
    from tpu9.observability.metrics import metrics as registry
    out = {}
    for key, summ in registry.summaries.items():
        if key.startswith("tpu9_startup_phase_s"):
            snap = summ.snapshot()
            phase = key.split('phase="')[-1].rstrip('"}')
            out[phase] = {"p50": round(snap["p50"], 4),
                          "p95": round(snap["p95"], 4),
                          "max": round(snap["max"], 4),
                          "n": snap["count"]}
    return out


def bench_cold_start_native(quick: bool = False) -> dict:
    """VERDICT round-2 item #2 + round-3 item #3: the REAL cold-start path —
    NativeRuntime containers (netns + overlay + pivot_root) started from a
    chunked image pulled through the content cache, not a bare
    ProcessRuntime echo. The image is GB-scale (multi-file) so the lazy
    path is what's actually measured: a cold pull must go ready on the
    sparse skeleton while the bulk streams in the background.

    Reports, each with phase-timeline evidence:
    - warm-node: bundle already materialized (the common autoscale cycle)
    - cold-pull: bundle deleted between trials; READY must precede full
      materialization, an on-demand faulted read must return real bytes,
      and cache counters prove chunks were re-fetched
    """
    import asyncio
    import shutil

    if os.geteuid() != 0:
        return {"coldstart_native_skipped": "requires root for NativeRuntime"}

    os.environ["TPU9_RUNTIME"] = "native"
    from tpu9.testing.localstack import LocalStack

    # payload = n_files × file_mb; 1 GiB full-run per VERDICT r03 #3
    n_files, file_mb = (8, 4) if quick else (256, 4)
    payload_mb = n_files * file_mb
    warm_trials = 3 if quick else 10
    pull_trials = 2 if quick else 5

    app = ("import hashlib, os\n"
           "def handler(op='', **kwargs):\n"
           "    blob = os.environ['BLOB_PATH']\n"
           "    if op == 'read':\n"
           "        data = open(blob, 'rb').read()\n"
           "        return {'sha': hashlib.sha256(data).hexdigest(),\n"
           "                'n': len(data)}\n"
           "    return {'blob_bytes': os.path.getsize(blob)}\n")

    async def run() -> dict:
        out: dict = {"runtime": "native", "image_payload_mb": payload_mb,
                     "image_files": n_files}
        violations: list[str] = []
        async with LocalStack() as stack:
            # quick mode's payload is smaller — keep it above the lazy
            # threshold either way (the lazy path IS the thing measured)
            stack.cfg.cache.lazy_threshold_mb = 16 if quick else 64
            status, img = await stack.api("POST", "/rpc/image/build", json_body={
                "commands": [f"mkdir -p env && i=0; while [ $i -lt {n_files} ]"
                             f"; do head -c {file_mb*1024*1024} /dev/urandom "
                             f"> env/blob$i.bin; i=$((i+1)); done"]})
            assert status == 200, img
            image_id = img["image_id"]
            for _ in range(6000):
                _, st = await stack.api("GET", f"/rpc/image/status/{image_id}")
                if st["status"] in ("ready", "failed"):
                    break
                await asyncio.sleep(0.1)
            if st["status"] != "ready":
                return {"coldstart_native_error": f"image build: {st}"}

            bundle = os.path.join(stack.cfg.cache.data_dir, "bundles",
                                  image_id)
            blob = os.path.join(bundle, "env", "blob3.bin")
            dep = await stack.deploy_endpoint(
                "native-imaged", {"app.py": app}, "app:handler",
                config_extra={
                    "runtime": {"image_id": image_id, "cpu_millicores": 1000,
                                "memory_mb": 1024},
                    "env": {"BLOB_PATH": blob}})

            t0 = time.perf_counter()
            first = await stack.invoke(dep, {"n": 0})
            out["first_deploy_s"] = round(time.perf_counter() - t0, 4)
            if first.get("blob_bytes") != file_mb * 1024 * 1024:
                violations.append(
                    f"coldstart_native: container did not see the image "
                    f"payload ({first})")

            warm = []
            for _ in range(warm_trials):
                await stack.scale_to_zero(dep)
                t0 = time.perf_counter()
                await stack.invoke(dep, {"n": 1})
                warm.append(time.perf_counter() - t0)
            out["cold_start_native_warmnode"] = _percentiles(warm)
            out["cold_start_native_p50_s"] = out[
                "cold_start_native_warmnode"]["p50"]

            # cold-pull tier: delete the bundle so materialization (from the
            # node cache store) is back on the path. Cache stats are summed
            # across ALL workers — the pool can run several and the timed
            # container may land on any of them (round-3 advisor finding:
            # reading workers[0] alone can fake a 'pull did not happen').
            workers = list(getattr(stack, "workers", None) or [])

            def cache_ops() -> int:
                return sum(sum(w.cache.client.stats.values())
                           for w in workers if getattr(w, "cache", None))

            async def fill_of(img):
                for w in workers:
                    f = w.cache.puller._fills.get(img)
                    if f is not None:
                        return f
                return None

            pulls = []
            fetch_counts = []
            ready_early = []      # ready BEFORE full materialization?
            for trial in range(pull_trials):
                await stack.scale_to_zero(dep)
                # let any in-flight fill finish before invalidating, so the
                # rmtree races nothing and each trial is a clean cold pull
                f = await fill_of(image_id)
                if f is not None:
                    await cancellable_wait(f.wait(), 300)
                shutil.rmtree(bundle, ignore_errors=True)
                # benchmark hygiene: the previous trial's 1 GiB fill
                # leaves dirty pages whose writeback otherwise bleeds
                # into this trial's timed window (observed ±0.5 s noise)
                await asyncio.to_thread(os.sync)
                await asyncio.sleep(0.3)
                before = cache_ops()
                t0 = time.perf_counter()
                await stack.invoke(dep, {"n": 2})
                pulls.append(time.perf_counter() - t0)
                ready_early.append(not os.path.exists(
                    os.path.join(bundle, ".tpu9-complete")))
                # ops counted over the whole pull CYCLE (timed invoke +
                # background fill): the boot gate intentionally defers
                # bulk fetches past container.ready, so the invoke window
                # alone may show ~0 ops on a healthy lazy pull
                f = await fill_of(image_id)
                if f is not None:
                    await cancellable_wait(f.wait(), 300)
                fetch_counts.append(cache_ops() - before)
            out["cold_start_native_pull"] = _percentiles(pulls)
            out["cold_start_native_pull_p50_s"] = out[
                "cold_start_native_pull"]["p50"]
            if workers and not any(c > 0 for c in fetch_counts):
                violations.append(
                    "coldstart_native: bundle deleted but zero cache "
                    "activity during re-pull — the pull did not happen")
            out["pull_cache_ops_per_trial"] = fetch_counts
            # lazy-load proofs (VERDICT r03 #3): readiness must not wait for
            # the whole image, and a gated on-demand read must return the
            # real bytes, not placeholder zeros
            out["pull_ready_before_complete"] = ready_early
            # at GB scale the fill takes many seconds — ready MUST win the
            # race; quick mode's small payload can legitimately fill first
            if not quick and not all(ready_early):
                violations.append(
                    "coldstart_native: container.ready waited for full "
                    "materialization — lazy path not in effect")
            read = await stack.invoke(dep, {"op": "read"})
            import hashlib
            manifest = await stack._manifest_fetch(image_id)
            entry = next(e for e in manifest.files
                         if e.path == "env/blob3.bin")
            want_chunks = []
            for c in entry.chunks:
                for w in workers:
                    data = await w.cache.client.get(c)
                    if data is not None:
                        want_chunks.append(data)
                        break
            want = hashlib.sha256(b"".join(want_chunks)).hexdigest()
            out["ondemand_read_sha_ok"] = read.get("sha") == want
            if not out["ondemand_read_sha_ok"]:
                violations.append(
                    "coldstart_native: on-demand faulted read returned "
                    "wrong bytes")
            f = await fill_of(image_id)
            if f is not None:
                await cancellable_wait(f.wait(), 600)
                out["lazy_fill_stats"] = dict(f.stats)
            out["phase_timeline"] = _phase_report()
        out["violations"] = violations
        out["valid"] = not violations
        return out

    return asyncio.run(run())


_JAX_RESTORE_APP = (
    "import jax, jax.numpy as jnp\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    for _ in range(8):\n"
    "        x = jnp.tanh(x @ x.T) + x\n"
    "    return x.sum()\n"
    "X = jnp.ones((256, 256), jnp.bfloat16)\n"
    "Y0 = float(f(X))          # compile at import: the cold-start cost\n"
    "def handler(**kwargs):\n"
    "    return {'y': float(f(X)), 'backend': jax.default_backend(),\n"
    "            'kind': jax.devices()[0].device_kind}\n")


def _bench_jax_restore(phase: str, container_env: dict, cache_dir: str,
                       tpu: str, trials: int, suffix: str,
                       invoke_timeout: float) -> tuple[dict, list, dict]:
    """Shared core of the JAX cold-start phases: deploy the compile-at-import
    app (on the worker's real chip when ``tpu`` names a slice), first invoke
    (cold compile), check the persistent cache filled, then N scale-to-zero
    → invoke restore trials. Returns (out, violations, first_reply) — the
    caller owns backend validation."""
    import asyncio

    from tpu9.testing.localstack import LocalStack

    async def run():
        out: dict = {}
        violations: list[str] = []
        async with LocalStack(pool_tpu_type=tpu) as stack:
            config = {"timeout_s": invoke_timeout, "env": container_env}
            if tpu:
                await stack._worker_factory(tpu_generation="v5e")
                config["runtime"] = {"tpu": tpu}
            dep = await stack.deploy_endpoint(
                "jax-restore" + suffix.replace("_", "-"),
                {"app.py": _JAX_RESTORE_APP}, "app:handler",
                config_extra=config)
            t0 = time.perf_counter()
            first = await stack.invoke(dep, {}, timeout=invoke_timeout)
            out[f"cold_start_jax_first{suffix}_s"] = round(
                time.perf_counter() - t0, 4)
            assert "y" in first, first
            cached = sum(len(fs) for _, _, fs in os.walk(cache_dir))
            out[f"jax_cache_entries{suffix}"] = cached
            if cached == 0:
                violations.append(
                    f"{phase}: no persistent-cache entries written — "
                    "restore trials would be re-measuring cold compiles")
            restores = []
            for _ in range(trials):
                await stack.scale_to_zero(dep)
                t0 = time.perf_counter()
                await stack.invoke(dep, {}, timeout=invoke_timeout)
                restores.append(time.perf_counter() - t0)
            out[f"cold_start_jax_restore{suffix}"] = _percentiles(restores)
            out[f"cold_start_jax_restore{suffix}_p50_s"] = out[
                f"cold_start_jax_restore{suffix}"]["p50"]
        return out, violations, first

    return asyncio.run(run())


def bench_cold_start_jax(quick: bool = False) -> dict:
    """Cold start of a JAX container with persistent-compile-cache restore:
    first boot pays the XLA compile; every later cold start restores the
    executable from JAX_COMPILATION_CACHE_DIR (the real TPU cold-start tail
    is compile time — SURVEY.md §7 hard-part #2)."""
    cache_dir = _cold_cache_dir("coldstart-jax")
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": cache_dir,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    out, violations, _ = _bench_jax_restore(
        "coldstart_jax", env, cache_dir, "", trials=3 if quick else 10,
        suffix="", invoke_timeout=300.0)
    out["violations"] = violations
    out["valid"] = not violations
    return out


def bench_cold_start_stream(quick: bool = False) -> dict:
    """Weight-streaming restore (ISSUE 1 tentpole): the same checkpoint
    restored through the three tiers on one node —

    - **classic**: cache → workdir materialize → re-read → deserialize →
      ``jax.device_put`` (the chain every restore used to pay)
    - **streamed**: cache → preallocated host buffer → device, fetch of
      shard *i+1* overlapped with device transfer of shard *i*
    - **warm pool**: deserialized host tree already resident (λScale
      keep-alive) → device only

    Emits per-phase evidence straight from
    ``CheckpointManager.last_restore_metrics`` (``weight_stream_fetch_s``,
    ``weight_stream_put_s``, ``warm_pool_hit``) and FAILS itself if the
    tiers don't strictly order warm < streamed < classic on p50."""
    import asyncio
    import shutil
    import tempfile

    import numpy as np

    async def run() -> dict:
        from tpu9.cache import CacheClient, DiskStore
        from tpu9.serving import weights as wfmt
        from tpu9.worker.checkpoint import CheckpointManager
        from tpu9.worker.weightpool import WeightPool

        out: dict = {}
        violations: list[str] = []
        tmp = tempfile.mkdtemp(prefix="tpu9-bench-stream-")
        try:
            import jax

            rng = np.random.default_rng(0)
            n_shards = 4 if quick else 8
            shard_mb = 4 if quick else 8
            tree = {"model": {"blocks": [
                rng.standard_normal(shard_mb << 18, dtype=np.float32)
                for _ in range(n_shards)], "step": 1234}}
            src = os.path.join(tmp, "src")
            os.makedirs(src)
            wfmt.save_params(tree, os.path.join(src, "params.tpu9w"))
            with open(os.path.join(src, "app.py"), "w") as f:
                f.write("# handler code rides the classic path\n")

            store = DiskStore(os.path.join(tmp, "cache"),
                              max_bytes=8 << 30)

            async def peers():
                return []

            client = CacheClient(store, peers)
            manifests: dict = {}

            async def record(stub, ws, cid):
                return "ckpt-stream-bench"

            async def store_manifest(cid, blob):
                manifests[cid] = blob

            async def fetch_manifest(cid):
                return manifests.get(cid)

            pool = WeightPool(2 << 30)
            cm = CheckpointManager(client, record=record,
                                   store_manifest=store_manifest,
                                   fetch_manifest=fetch_manifest,
                                   weight_pool=pool)
            ckpt = await cm.create("stub", "ws", "c0", src)
            assert ckpt, "checkpoint create failed"
            total_bytes = sum(a.nbytes for a in tree["model"]["blocks"])
            out["weight_stream_checkpoint_mb"] = total_bytes >> 20

            def to_device(tree_or_arrays):
                dev = jax.device_put(tree_or_arrays)
                return jax.block_until_ready(dev)

            trials = 3 if quick else 5
            cm_classic = CheckpointManager(client,
                                           fetch_manifest=fetch_manifest,
                                           stream_weights=False)
            classic = []
            for i in range(trials):
                dest = os.path.join(tmp, f"classic{i}")
                t0 = time.perf_counter()
                assert await cm_classic.restore(ckpt, dest)
                loaded = wfmt.load_params(
                    os.path.join(dest, "params.tpu9w"))
                to_device(loaded)
                classic.append(time.perf_counter() - t0)
                shutil.rmtree(dest)
            out["cold_start_classic_restore"] = _percentiles(classic)
            out["cold_start_classic_restore_p50_s"] = out[
                "cold_start_classic_restore"]["p50"]

            streamed, fetch_s, put_s = [], [], []
            decomp: list = []
            for i in range(trials):
                pool.clear()                      # every trial is Nth=1
                t0 = time.perf_counter()
                trees, metrics = await cm.restore_params(ckpt)
                streamed.append(time.perf_counter() - t0)
                assert trees and not metrics["warm_pool_hit"]
                fetch_s.append(metrics["weight_stream_fetch_s"])
                put_s.append(metrics["weight_stream_put_s"])
                decomp.append(metrics)
            out["cold_start_jax_restore_stream"] = _percentiles(streamed)
            out["cold_start_jax_restore_stream_p50_s"] = out[
                "cold_start_jax_restore_stream"]["p50"]
            out["weight_stream_fetch_s"] = round(
                statistics.median(fetch_s), 4)
            out["weight_stream_put_s"] = round(statistics.median(put_s), 4)

            # ---- cold-start decomposition + trace cross-check (ISSUE
            # 13): per-trial fetch/consume WINDOWS from the restore
            # record's interval anchors, and the same intervals read back
            # from the restore.request span tree the restore emitted —
            # two independent pipelines (record dict vs tracer ring /
            # wall-anchor arithmetic) that must agree within 10%, the
            # same artifact a LocalStack cold start serves at
            # /api/v1/coldstart and /api/v1/traces.
            from tpu9.observability import coldstart as cs_mod
            from tpu9.observability.trace import tracer as _tracer

            def windows(m: dict) -> tuple[float, float]:
                fw = pw = 0.0
                for g in m.get("groups_detail", []):
                    if g.get("fetch_iv"):
                        fw += g["fetch_iv"][1] - g["fetch_iv"][0]
                    if g.get("put_iv"):
                        pw += g["put_iv"][1] - g["put_iv"][0]
                return fw, pw
            fetch_w = [windows(m)[0] for m in decomp]
            put_w = [windows(m)[1] for m in decomp]
            out["coldstart_fetch_window_s"] = round(
                statistics.median(fetch_w), 4)
            out["coldstart_put_window_s"] = round(
                statistics.median(put_w), 4)
            out["coldstart_overlap_frac"] = round(statistics.median(
                [m.get("overlap_frac", 0.0) for m in decomp]), 4)
            out["coldstart_plan_s"] = round(statistics.median(
                [m.get("plan_s", 0.0) for m in decomp]), 4)
            out["coldstart_bytes_by_tier"] = decomp[-1].get("tiers", {})
            # per-EDGE peer split (ISSUE 17 satellite 6): which serving
            # replica fed which bytes — empty here (no peers in this
            # phase) but present, so the field's shape is exercised on
            # every round, not only when the scaleout phase runs
            out["coldstart_bytes_by_edge"] = decomp[-1].get("peer_bytes",
                                                            {})
            out["coldstart_hedge"] = decomp[-1].get("hedge", {})

            last = decomp[-1]
            traced = cs_mod.decompose_spans(
                _tracer.export(trace_id=last.get("trace_id", "")))
            mf, mp = windows(last)
            dis = max(cs_mod.agreement(traced["fetch_s"], mf),
                      cs_mod.agreement(traced["device_put_s"], mp))
            out["coldstart_trace_decomposition"] = traced
            out["coldstart_trace_disagreement"] = round(dis, 4)
            if dis > 0.10:
                violations.append(
                    f"coldstart_stream: traced span intervals disagree "
                    f"with the measured restore intervals by {dis:.1%} "
                    f"(gate 10%) — fetch {traced['fetch_s']:.4f}s vs "
                    f"{mf:.4f}s, put {traced['device_put_s']:.4f}s vs "
                    f"{mp:.4f}s")
            if out["coldstart_overlap_frac"] <= 0.0:
                violations.append(
                    "coldstart_stream: zero fetch-consume overlap — the "
                    "double-buffered pipeline is running serial")

            warm, hits = [], []
            for i in range(trials):               # pool stays warm
                t0 = time.perf_counter()
                trees, metrics = await cm.restore_params(ckpt)
                warm.append(time.perf_counter() - t0)
                hits.append(bool(metrics["warm_pool_hit"]))
            out["cold_start_warm_pool_restore"] = _percentiles(warm)
            out["cold_start_warm_pool_restore_p50_s"] = out[
                "cold_start_warm_pool_restore"]["p50"]
            out["warm_pool_hit"] = all(hits)
            out["weight_pool_stats"] = pool.snapshot()
            out["cache_stats"] = {k: v for k, v in
                                  client.snapshot().items()
                                  if k not in ("peers", "hist_buckets_s")}

            if not all(hits):
                violations.append(
                    "coldstart_stream: warm-pool trials missed the pool — "
                    "the keep-alive tier is not engaging")
            if out["cold_start_warm_pool_restore_p50_s"] >= \
                    out["cold_start_jax_restore_stream_p50_s"]:
                violations.append(
                    "coldstart_stream: warm-pool restore not faster than "
                    "cold streamed restore")
            if out["cold_start_jax_restore_stream_p50_s"] >= \
                    out["cold_start_classic_restore_p50_s"]:
                violations.append(
                    "coldstart_stream: streamed restore not faster than "
                    "the classic workdir chain")
            await client.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        out["violations"] = violations
        out["valid"] = not violations
        return out

    return asyncio.run(run())


def bench_scaleout(quick: bool = False) -> dict:
    """Scale-out plane (ISSUE 17): N replicas join one deployment and
    restore the same multi-group checkpoint —

    - **serial baseline**: each joiner alone, no peers — every byte from
      the source tier (the pre-tree world: source bytes grow N×)
    - **tree**: real ChunkServers per replica, edges planned by the real
      :class:`ScaleoutCoordinator` over advertised groups, joiners
      staggered by tree depth and re-serving every group they consume —
      source-tier bytes must stay sub-linear in N (HARD) and the
      concurrent 1→N bring-up must beat N× serial
    - **execute-while-scaling**: per-group ``on_group`` readiness drives
      the router's real ``_scaleout_admit`` fence mid-restore — a
      group-hinted request is admitted BEFORE the final group lands, an
      un-hinted one is fenced out
    - **chaos**: one more joiner restores while ``tree_peer_loss`` kills
      its primary parent mid-transfer — the hedged read must fall
      through the surviving preference list with zero failed restores
      and no new source traffic (every group has live holders)."""
    import asyncio
    import shutil
    import tempfile
    import threading

    import numpy as np

    async def run() -> dict:
        from tpu9.cache import CacheClient, DiskStore
        from tpu9.cache.server import ChunkServer
        from tpu9.scaleout.coordinator import ScaleoutCoordinator, \
            build_report
        from tpu9.serving import weights as wfmt
        from tpu9.worker.checkpoint import CheckpointManager

        out: dict = {}
        violations: list[str] = []
        tmp = tempfile.mkdtemp(prefix="tpu9-bench-scaleout-")
        seed_client = seed_srv = None
        threads: list[threading.Thread] = []
        stop_evt = threading.Event()
        try:
            rng = np.random.default_rng(7)
            n_groups = 3 if quick else 4
            n_shards = 2 if quick else 3
            shard_mb = 2 if quick else 4
            n_join = 4
            src = os.path.join(tmp, "src")
            os.makedirs(src)
            for g in range(n_groups):
                tree = {"blk": [rng.standard_normal(shard_mb << 18,
                                                    dtype=np.float32)
                                for _ in range(n_shards)]}
                wfmt.save_params(tree, os.path.join(src, f"g{g}.tpu9w"))
            total_bytes = n_groups * n_shards * (shard_mb << 20)
            out["scaleout_groups"] = n_groups
            out["scaleout_replicas"] = n_join
            out["scaleout_checkpoint_mb"] = total_bytes >> 20

            manifests: dict = {}

            async def record(stub, ws, cid):
                return "ckpt-scaleout-bench"

            async def store_manifest(cid, blob):
                manifests[cid] = blob

            async def fetch_manifest(cid):
                return manifests.get(cid)

            def ident(entry, arr):
                # the phase measures the transfer plane, not device_put
                return arr

            # the SEED replica: creates the checkpoint (its store is the
            # only replica-side copy), then restores once from its local
            # tier so its client ADVERTISES every group
            seed_store = DiskStore(os.path.join(tmp, "seed"),
                                   max_bytes=8 << 30)

            async def no_peers():
                return []

            seed_client = CacheClient(seed_store, no_peers)
            seed_cm = CheckpointManager(seed_client, record=record,
                                        store_manifest=store_manifest,
                                        fetch_manifest=fetch_manifest)
            ckpt = await seed_cm.create("stub", "ws", "seed", src)
            assert ckpt, "checkpoint create failed"
            trees, _ = await seed_cm.restore_params(ckpt, device_put=ident)
            assert trees and len(trees) == n_groups
            group_keys = sorted(seed_client.groups)
            assert len(group_keys) == n_groups, "seed advertised " \
                f"{len(group_keys)}/{n_groups} groups"
            seed_srv = await ChunkServer(
                seed_store, port=0,
                groups_fn=lambda: seed_client.groups).start()
            seed_client.self_address = seed_srv.address
            seed_addr = seed_srv.address

            # the source tier (object store stand-in): serves chunk bytes
            # out of the seed's store but is counted as SOURCE by every
            # client that falls through to it, at object-store-class
            # per-connection bandwidth — unthrottled it would be a local
            # disk read, faster than any real S3/GCS GET and faster than
            # the peer plane's real TCP transfers, making the serial
            # baseline a fantasy the tree could never beat. Thread-loop
            # safe: DiskStore only touches its asyncio.Lock on eviction,
            # which an 8 GiB cap over ~100 MiB of chunks never reaches.
            SRC_BW = 48 << 20    # bytes/s per connection

            async def source_fn(digest):
                data = await seed_store.get(digest)
                if data is not None:
                    await asyncio.sleep(len(data) / SRC_BW)
                return data

            # each replica runs in its OWN thread with its own event loop
            # — one shared loop would serialize the "concurrent" bring-up
            # and the CPU-scaled bound could never hold (in production
            # these are separate processes)
            def in_thread(coro_fn, *args):
                return asyncio.to_thread(
                    lambda: asyncio.run(coro_fn(*args)))

            # ---- serial no-peer baseline: N joiners, one at a time,
            # every byte from source — the pre-tree cost the headline
            # ratios are judged against
            async def serial_one(i: int) -> tuple:
                st = DiskStore(os.path.join(tmp, f"ser{i}"),
                               max_bytes=8 << 30)
                cl = CacheClient(st, no_peers, source=source_fn)
                cm = CheckpointManager(cl,
                                       fetch_manifest=fetch_manifest)
                t0 = time.perf_counter()
                trees, _m = await cm.restore_params(ckpt,
                                                    device_put=ident)
                wall = time.perf_counter() - t0
                ok = bool(trees and len(trees) == n_groups)
                nsrc = cl.stats["bytes_source"]
                await cl.close()
                return wall, nsrc, ok

            serial_walls: list[float] = []
            serial_source = 0
            for i in range(n_join):
                wall, nsrc, ok = await in_thread(serial_one, i)
                assert ok, f"serial baseline restore {i} failed"
                serial_walls.append(wall)
                serial_source += nsrc
                await asyncio.to_thread(
                    shutil.rmtree, os.path.join(tmp, f"ser{i}"),
                    ignore_errors=True)
            single_wall = statistics.median(serial_walls)
            serial_total = sum(serial_walls)
            out["scaleout_single_restore_s"] = round(single_wall, 4)
            out["scaleout_serial_total_s"] = round(serial_total, 4)
            out["scaleout_source_bytes_serial"] = serial_source

            # ---- tree leg: the real coordinator plans edges over the
            # advertised groups; every joiner runs a live ChunkServer and
            # re-serves what it consumes. Protocol: each thread brings up
            # its server, parks until the coordinator (main thread) has
            # planned over the full membership, restores along its edges
            # with a depth stagger, then KEEPS SERVING (for descendants
            # and the chaos leg) until stop_evt.
            addr_box: list = [None] * n_join
            addr_evts = [threading.Event() for _ in range(n_join)]
            clients_box: list = [None] * n_join
            shared: dict = {}
            plan_evt = threading.Event()
            results: dict[int, dict] = {}

            async def joiner_main(i: int) -> None:
                st = DiskStore(os.path.join(tmp, f"join{i}"),
                               max_bytes=8 << 30)

                async def peers():
                    return [seed_addr] + [a for a in addr_box if a]

                cl = CacheClient(st, peers, source=source_fn)
                srv = await ChunkServer(
                    st, port=0, groups_fn=lambda: cl.groups).start()
                cl.self_address = srv.address
                clients_box[i] = cl
                addr_box[i] = srv.address
                addr_evts[i].set()
                try:
                    while not plan_evt.is_set():
                        await asyncio.sleep(0.005)
                    plan = shared["plan"]
                    lag = (shared["depth"].get(srv.address, 1) - 1) \
                        * shared["stagger"] \
                        - (time.perf_counter() - shared["t0"])
                    if lag > 0:
                        await asyncio.sleep(lag)

                    async def hints(key, _a=srv.address):
                        return plan.peer_prefs(_a, key)

                    cm = CheckpointManager(cl,
                                           fetch_manifest=fetch_manifest,
                                           tree_hints=hints)
                    res: dict = {"start_mono": time.perf_counter()}

                    def on_group(group, tree, done, total):
                        res.setdefault("first_group_mono",
                                       time.perf_counter())
                        res.setdefault("first_group", group)

                    trees, m = await cm.restore_params(
                        ckpt, device_put=ident, on_group=on_group)
                    res["done_mono"] = time.perf_counter()
                    res["ok"] = bool(trees and len(trees) == n_groups)
                    res["metrics"] = m
                    results[i] = res
                    while not stop_evt.is_set():
                        await asyncio.sleep(0.02)
                finally:
                    await cl.close()
                    await srv.stop()

            threads = [threading.Thread(
                target=lambda i=i: asyncio.run(joiner_main(i)),
                daemon=True) for i in range(n_join)]
            for t in threads:
                t.start()
            for ev in addr_evts:
                ok = await asyncio.to_thread(ev.wait, 60)
                assert ok, "joiner cache server never came up"

            coord = ScaleoutCoordinator()
            coord.observe_worker("seed",
                                 {"cache": seed_client.snapshot()})
            for i, a in enumerate(addr_box):
                coord.observe_worker(f"join{i}",
                                     {"cache": {"addr": a, "groups": []}})
            plan = coord.refresh()
            out["scaleout_tree_edges"] = len(plan.edges())
            out["scaleout_tree_source_edges"] = \
                sum(1 for _, _, p in plan.edges() if p == "@source")
            if out["scaleout_tree_source_edges"]:
                violations.append(
                    "scaleout: planner minted source edges with a live "
                    "seed holding every group")

            def depth_of(addr: str) -> int:
                d, cur, seen = 0, addr, set()
                while cur not in (seed_addr, "", "@source") \
                        and cur not in seen and d <= n_join:
                    seen.add(cur)
                    pref = plan.peer_prefs(cur, group_keys[0])
                    cur = pref[0] if pref else ""
                    d += 1
                return d

            shared["plan"] = plan
            shared["depth"] = {a: depth_of(a) for a in addr_box}
            # head start per tree depth so a child mostly streams from
            # its parent instead of falling back to the seed — sized to
            # PEER transfer time (loopback TCP), not the source-throttled
            # single-restore wall
            shared["stagger"] = 0.05
            shared["t0"] = time.perf_counter()
            plan_evt.set()
            deadline = time.perf_counter() + 240
            while len(results) < n_join:
                assert time.perf_counter() < deadline, \
                    f"tree bring-up stalled ({len(results)}/{n_join})"
                await asyncio.sleep(0.01)
            tree_wall = max(r["done_mono"] for r in results.values()) \
                - shared["t0"]
            failed = [i for i, r in results.items() if not r["ok"]]
            assert not failed, f"tree restores failed: {failed}"

            tree_source = sum(cl.stats["bytes_source"]
                              for cl in clients_box)
            tree_peer = sum(cl.stats["bytes_peer"] for cl in clients_box)
            edge_bytes: dict[str, int] = {}
            for r in results.values():
                for addr, n in r["metrics"].get("peer_bytes",
                                                {}).items():
                    edge_bytes[addr] = edge_bytes.get(addr, 0) + n
            nonseed = sum(n for a, n in edge_bytes.items()
                          if a != seed_addr)
            out["scaleout_tree_wall_s"] = round(tree_wall, 4)
            out["scaleout_bringup_ratio"] = round(
                tree_wall / single_wall, 4) if single_wall > 0 else 0.0
            out["scaleout_serial_speedup"] = round(
                serial_total / tree_wall, 4) if tree_wall > 0 else 0.0
            out["scaleout_source_bytes_tree"] = tree_source
            out["scaleout_peer_bytes_tree"] = tree_peer
            out["scaleout_source_bytes_ratio"] = round(
                tree_source / serial_source, 4) if serial_source else 1.0
            out["scaleout_bytes_by_edge"] = edge_bytes
            out["scaleout_nonseed_peer_bytes"] = nonseed

            # O(1)-source (HARD): N joiners over the tree must not pull
            # anywhere near the serial N× from the source tier
            if out["scaleout_source_bytes_ratio"] >= 0.6:
                violations.append(
                    f"scaleout: source tier served "
                    f"{out['scaleout_source_bytes_ratio']:.0%} of the "
                    f"serial baseline bytes across {n_join} joiners — "
                    "the tree is not keeping source traffic O(1)")
            # CPU-scaled bring-up gate: with the source tier at object
            # -store bandwidth the single restore is transfer-bound, so
            # the concurrent 1→N bring-up must land near 1× — scaled by
            # the core deficit, because N replicas hashing/framing on
            # K < N cores genuinely serialize that much of the work
            cores = os.cpu_count() or 1
            bound = 1.6 * max(1.0, n_join / min(cores, n_join))
            out["scaleout_bringup_bound"] = round(bound, 3)
            if out["scaleout_bringup_ratio"] > bound:
                violations.append(
                    f"scaleout: concurrent 1→{n_join} bring-up took "
                    f"{out['scaleout_bringup_ratio']:.2f}× a single "
                    f"restore (bound {bound:.2f}× on {cores} cores)")

            # ---- execute-while-scaling: the real router fence, driven
            # by the per-group readiness the restores just reported —
            # judged on the LAST joiner to finish (the worst case)
            from tpu9.router.fleet import FleetRouter
            ews_i = max(results, key=lambda i: results[i]["done_mono"])
            r = results[ews_i]
            span = r["done_mono"] - r["start_mono"]
            first_frac = ((r["first_group_mono"] - r["start_mono"])
                          / span if span > 0 else 1.0)
            out["scaleout_first_group_frac"] = round(first_frac, 4)
            first_group = r["first_group"]
            readiness = {"r0": (1.0 / n_groups, {first_group})}
            hinted = json.dumps(
                {"weight_groups": [first_group]}).encode()
            admitted = FleetRouter._scaleout_admit(hinted, ["r0"],
                                                   readiness)
            fenced = FleetRouter._scaleout_admit(b"{}", ["r0"],
                                                 readiness)
            out["scaleout_partial_admitted"] = admitted == ["r0"]
            out["scaleout_unhinted_fenced"] = fenced == []
            out["scaleout_first_admit_before_complete"] = bool(
                admitted == ["r0"] and 0.0 < first_frac < 1.0)
            if not out["scaleout_first_admit_before_complete"]:
                violations.append(
                    "scaleout: execute-while-scaling never admitted a "
                    "group-hinted request before the final group landed "
                    f"(first-group frac {first_frac:.2f}, admitted "
                    f"{admitted})")
            if not out["scaleout_unhinted_fenced"]:
                violations.append(
                    "scaleout: an un-hinted request was admitted to a "
                    "partially-ready replica — the fence leaks")

            # ---- chaos leg: one more joiner plans real tree edges, then
            # tree_peer_loss kills its primary parent mid-transfer; the
            # hedged read must fall through the surviving preference list
            for i, cl in enumerate(clients_box):
                coord.observe_worker(f"join{i}",
                                     {"cache": cl.snapshot()})
            chaos_addr = "127.0.0.1:1"   # plan identity only; never serves
            coord.observe_worker("chaos",
                                 {"cache": {"addr": chaos_addr,
                                            "groups": []}})
            plan = coord.refresh()
            probe = plan.peer_prefs(chaos_addr, group_keys[0])
            assert probe, "chaos joiner got no tree edges"
            victim = probe[0]
            out["scaleout_chaos_victim"] = victim
            out["scaleout_chaos_backups"] = len(probe) - 1

            async def all_peers():
                return [seed_addr] + [a for a in addr_box if a]

            async def chaos_hints(key):
                return plan.peer_prefs(chaos_addr, key)

            chaos_store = DiskStore(os.path.join(tmp, "chaos"),
                                    max_bytes=8 << 30)
            # the fault plane arms at client CONSTRUCTION — set the env
            # first, like a real worker booting into a chaos run
            os.environ["TPU9_FAULTS"] = \
                f"tree_peer_loss:peer={victim},after_calls=2"
            try:
                chaos_cl = CacheClient(chaos_store, all_peers,
                                       source=source_fn)
                chaos_cl.self_address = chaos_addr
            finally:
                os.environ.pop("TPU9_FAULTS", None)
            chaos_ok = False
            try:
                chaos_cm = CheckpointManager(
                    chaos_cl, fetch_manifest=fetch_manifest,
                    tree_hints=chaos_hints)
                t0c = time.perf_counter()
                trees, _m = await chaos_cm.restore_params(
                    ckpt, device_put=ident)
                out["scaleout_chaos_restore_s"] = round(
                    time.perf_counter() - t0c, 4)
                chaos_ok = bool(trees and len(trees) == n_groups)
            except Exception as exc:   # noqa: BLE001 — a failed restore
                                       # IS the violation being tested
                out["scaleout_chaos_error"] = \
                    f"{type(exc).__name__}: {exc}"
            out["scaleout_chaos_restore_ok"] = chaos_ok
            out["scaleout_chaos_peer_errors"] = \
                chaos_cl.stats["peer_errors"]
            out["scaleout_chaos_source_bytes"] = \
                chaos_cl.stats["bytes_source"]
            await chaos_cl.close()
            if not chaos_ok:
                violations.append(
                    "scaleout: chaos restore FAILED under tree_peer_loss "
                    "— peer death must fall through to survivors, never "
                    "fail the restore")
            if chaos_ok and not chaos_cl.stats["peer_errors"]:
                violations.append(
                    "scaleout: tree_peer_loss never fired — the chaos "
                    "leg tested nothing")
            if chaos_ok and chaos_cl.stats["bytes_source"] > 0:
                violations.append(
                    "scaleout: chaos restore fell back to SOURCE while "
                    "live peers held every group — re-plan must prefer "
                    "surviving holders")

            # evidence artifact: the same report /api/v1/scaleout serves
            out["scaleout_report"] = build_report(
                coord.ledger.snapshot(), plan)
            out["scaleout_coordinator"] = coord.stats()
        finally:
            stop_evt.set()
            for t in threads:
                await asyncio.to_thread(t.join, 30)
            if seed_client is not None:
                try:
                    await seed_client.close()
                except Exception:   # noqa: BLE001 — teardown
                    pass
            if seed_srv is not None:
                try:
                    await seed_srv.stop()
                except Exception:   # noqa: BLE001 — teardown
                    pass
            await asyncio.to_thread(shutil.rmtree, tmp, ignore_errors=True)
        out["violations"] = violations
        out["valid"] = not violations
        return out

    return asyncio.run(run())


def bench_cold_start_jax_tpu(quick: bool = False) -> dict:
    """On-CHIP JAX restore cold start (VERDICT r04 next-round #1): same
    restore loop as ``bench_cold_start_jax`` but the container is deployed
    with ``tpu="v5e-1"`` and so runs on the worker's real chip — the
    measured p50 includes libtpu/PJRT init and the persistent-compile-cache
    restore on the hardware, which the CPU-host number structurally cannot
    show. Parent stays forced-CPU like ``bench_llm_endpoint``; the
    container reports its backend and the phase rejects an off-chip run."""
    cpu_forced = os.environ.get("TPU9_BENCH_CPU") == "1"

    from tpu9.utils import force_cpu
    force_cpu(host_devices=0)      # this process must never hold the chip

    cache_dir = _cold_cache_dir("coldstart-jax-tpu")
    container_env = {
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    out, violations, first = _bench_jax_restore(
        "coldstart_jax_tpu", container_env, cache_dir,
        "" if cpu_forced else "v5e-1",
        trials=2 if quick else 3, suffix="_tpu", invoke_timeout=600.0)
    backend = (first.get("backend") or "").lower()
    out["jax_restore_tpu_container_on_tpu"] = backend == "tpu"
    out["jax_restore_tpu_backend"] = backend
    out["jax_restore_tpu_device_kind"] = first.get("kind", "")
    # this phase exists ONLY to produce on-chip numbers: an off-chip p50
    # must never ship under the _tpu key
    if not cpu_forced and backend != "tpu":
        violations.append(
            f"coldstart_jax_tpu: container backend is '{backend}' (kind "
            f"'{first.get('kind', '')}'), not a TPU — the restore numbers "
            "would not be on-chip")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: fleet router (ISSUE 2) — p50/p99 TTFT under mixed-tenant load with
# affinity on vs off, and shed behavior under overload. Drives the REAL
# FleetRouter (fair queue, affinity table, admission, signals) against a
# simulated replica fleet whose service time models KV prefix reuse: a
# replica serving a prompt whose prefix it has cached skips the prefill
# cost. Pure asyncio, CPU-only, deterministic seed.
# ---------------------------------------------------------------------------

def bench_router(quick: bool = False) -> dict:
    import asyncio
    import random as _random

    from tpu9.abstractions.common.buffer import ForwardResult
    from tpu9.config import RouterConfig
    from tpu9.router import FleetRouter
    from tpu9.statestore import MemoryStore
    from tpu9.types import ContainerState, ContainerStatus, Stub, StubConfig

    N_REPLICAS = 4
    N_REQUESTS = 120 if quick else 400
    N_GROUPS = 12            # distinct shared prefixes in the workload
    CACHE_GROUPS = 4         # per-replica KV capacity, in prefix groups
    BASE_MS = 2.0            # decode/dispatch floor per request
    PREFILL_MS = 10.0        # full prefill when the prefix is NOT cached
    STAGGER_MS = 1.5         # request inter-arrival

    class FakeFleet:
        def __init__(self, n):
            self.states = [ContainerState(
                container_id=f"r{i}", stub_id="s",
                status=ContainerStatus.RUNNING.value,
                address=f"127.0.0.1:{9000 + i}") for i in range(n)]

        async def containers_by_stub(self, stub_id, status=None):
            return list(self.states)

    def build_workload():
        """Mixed tenants: one flooding tenant (60% of traffic, long
        prompts), two light tenants. Seeded — both routing modes see the
        IDENTICAL sequence."""
        rng = _random.Random(1994)
        out = []
        for i in range(N_REQUESTS):
            r = rng.random()
            tenant = "flood" if r < 0.6 else ("chat-b" if r < 0.8 else "chat-c")
            group = rng.randrange(N_GROUPS)
            prefix = [group * 1000 + t for t in range(64)]   # 4 blocks of 16
            body = json.dumps({"tokens": prefix + [90000 + i],
                               "max_new_tokens": 16,
                               "_group": group}).encode()
            out.append((tenant, group, body))
        return out

    async def run_mode(affinity_on: bool) -> dict:
        cfg = RouterConfig(default_replica_inflight=4,
                           max_queue_depth=10000, max_queue_wait_s=30.0,
                           affinity_block_tokens=16)
        router = FleetRouter(cfg, MemoryStore(), FakeFleet(N_REPLICAS))
        if not affinity_on:
            rng = _random.Random(71)

            def random_order(body, replicas, load, saturated=None):
                out = list(replicas)
                rng.shuffle(out)
                return out

            router.affinity.order = random_order
        stub = Stub(stub_id="s", name="s", workspace_id="w",
                    config=StubConfig(timeout_s=60.0))
        # replica KV caches: group-granular LRU, bounded like a real pool
        caches: dict[str, list] = {f"r{i}": [] for i in range(N_REPLICAS)}
        hits = misses = 0

        def forward_for(group):
            async def forward(prefer):
                nonlocal hits, misses
                cid = prefer[0] if prefer else "r0"
                cache = caches[cid]
                if group in cache:
                    hits += 1
                    cache.remove(group)
                    cost_ms = BASE_MS
                else:
                    misses += 1
                    cost_ms = BASE_MS + PREFILL_MS
                    if len(cache) >= CACHE_GROUPS:
                        cache.pop(0)
                cache.append(group)
                await asyncio.sleep(cost_ms / 1000.0)
                return ForwardResult(status=200, body=b"{}",
                                     container_id=cid)
            return forward

        workload = build_workload()
        ttfts: list[float] = []

        async def one(tenant, group, body):
            t0 = time.monotonic()
            res = await router.submit(stub, tenant, body, forward_for(group))
            assert res.status == 200
            ttfts.append((time.monotonic() - t0) * 1000.0)

        tasks = []
        for tenant, group, body in workload:
            tasks.append(asyncio.create_task(one(tenant, group, body)))
            await asyncio.sleep(STAGGER_MS / 1000.0)
        await asyncio.gather(*tasks)
        await router.stop()
        ttfts.sort()
        total = hits + misses
        return {
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 3),
            "ttft_p99_ms": round(ttfts[int(len(ttfts) * 0.99) - 1], 3),
            "kv_hit_rate": round(hits / total, 4) if total else 0.0,
            "router_hit_rate": round(
                router.affinity.stats()["hit_rate"], 4),
        }

    async def run_overload() -> dict:
        """Burst past a tiny admission window: shed rate + honest 429s."""
        cfg = RouterConfig(default_replica_inflight=1, max_queue_depth=2,
                           max_queue_wait_s=10.0)
        router = FleetRouter(cfg, MemoryStore(), FakeFleet(1))
        stub = Stub(stub_id="s", name="s", workspace_id="w",
                    config=StubConfig(timeout_s=60.0))

        async def slow_forward(prefer):
            await asyncio.sleep(0.05)
            return ForwardResult(status=200, body=b"{}", container_id="r0")

        body = json.dumps({"tokens": list(range(32))}).encode()
        results = await asyncio.gather(*[
            router.submit(stub, "burst", body, slow_forward)
            for _ in range(12)])
        await router.stop()
        shed = [r for r in results if r.status == 429]
        ok = [r for r in results if r.status == 200]
        bad_headers = [r for r in shed
                       if "Retry-After" not in dict(r.headers)]
        return {"shed_rate": round(len(shed) / len(results), 4),
                "served": len(ok), "shed": len(shed),
                "sheds_missing_retry_after": len(bad_headers)}

    async def run_all():
        return (await run_mode(affinity_on=True),
                await run_mode(affinity_on=False),
                await run_overload())

    aff, rand, overload = asyncio.run(run_all())

    out = {
        "router_ttft_p50_ms": aff["ttft_p50_ms"],
        "router_ttft_p99_ms": aff["ttft_p99_ms"],
        "router_ttft_random_p50_ms": rand["ttft_p50_ms"],
        "router_ttft_random_p99_ms": rand["ttft_p99_ms"],
        "router_kv_hit_rate": aff["kv_hit_rate"],
        "router_kv_hit_rate_random": rand["kv_hit_rate"],
        "router_prefix_hit_rate": aff["router_hit_rate"],
        "router_shed_rate": overload["shed_rate"],
        "router_overload_served": overload["served"],
        "router_requests": N_REQUESTS,
    }
    violations = []
    # affinity must not be slower than random routing (the whole point of
    # KV-aware placement is a better TTFT; 5% tolerance for jitter)
    if aff["ttft_p50_ms"] > rand["ttft_p50_ms"] * 1.05:
        violations.append(
            f"affinity p50 {aff['ttft_p50_ms']}ms slower than random "
            f"{rand['ttft_p50_ms']}ms")
    if aff["kv_hit_rate"] <= rand["kv_hit_rate"]:
        violations.append(
            f"affinity kv hit rate {aff['kv_hit_rate']} not better than "
            f"random {rand['kv_hit_rate']}")
    if overload["shed"] == 0 or overload["served"] == 0:
        violations.append("overload phase did not both shed and serve")
    if overload["sheds_missing_retry_after"]:
        violations.append(
            f"{overload['sheds_missing_retry_after']} sheds lacked "
            "Retry-After")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: request survivability under induced faults (ISSUE 15) — a
# simulated replica fleet driven through the REAL FleetRouter, the REAL
# gateway failover driver (survival.submit_with_failover) and the REAL
# watermark-splice machinery (survival.StreamResumption), with the
# deterministic fault plane (tpu9.testing.faults) scheduling replica
# crashes, stalls and RPC transport errors. Never imports jax.
#
# Gates (bench_guard): zero client-visible failed requests is HARD (a
# violation strips the headline fields, and faults_recovery_p95_s is in
# HARD_FIELDS so the stripped round FAILS); recovery-time p95 is guarded
# "down" across rounds. ISSUE 16 adds block-ship resume: the headline
# leg recovers by adopting shipped KV blocks, a kv-ship-off leg prices
# the re-prefill baseline it must beat at p95, and a kv_ship_error
# chaos leg proves the fallback degrades to re-prefill — never to a
# client-visible failure.
# ---------------------------------------------------------------------------

def bench_faults(quick: bool = False) -> dict:
    import asyncio

    from tpu9.abstractions.common.buffer import ForwardResult
    from tpu9.config import RouterConfig
    from tpu9.gateway import survival as sv
    from tpu9.router import FleetRouter
    from tpu9.statestore import MemoryStore
    from tpu9.testing.faults import FaultPlane, parse_spec
    from tpu9.types import ContainerState, ContainerStatus, Stub, StubConfig
    from tpu9.utils.backoff import BackoffPolicy

    N_REPLICAS = 3
    N_REQUESTS = 80 if quick else 240
    STAGGER_MS = 2.0              # request inter-arrival
    SERVICE_MS = 2.0              # healthy per-request service floor
    CRASH_DOWN_S = 0.1            # replica outage window after a crash
    STALL_S = 0.08                # wedged-dispatch latency (≫ healthy)
    # ISSUE 16: a failover retry must rebuild the victim's KV state on
    # the survivor — a full re-prefill of the delivered watermark, or an
    # O(blocks) adopt of shipped kvwire blocks. The gap between the two
    # is what block-ship resume buys. Magnitudes are the realistic ones
    # (and deliberately large enough to survive the p95 tail, which the
    # crash outage windows otherwise dominate): a multi-hundred-token
    # watermark at single-digit-k tok/s prefill is hundreds of ms; an
    # adopt is one hedged cache read + a device scatter.
    REPREFILL_S = 0.25            # watermark re-prefill on the survivor
    ADOPT_S = 0.004               # kvwire fetch + import_blocks splice

    class FakeFleet:
        def __init__(self, n):
            self.states = [ContainerState(
                container_id=f"r{i}", stub_id="s",
                status=ContainerStatus.RUNNING.value,
                address=f"127.0.0.1:{9100 + i}") for i in range(n)]

        async def containers_by_stub(self, stub_id, status=None):
            return list(self.states)

    async def run(kv_ship: bool, ship_faults: bool) -> dict:
        # deterministic fault plan: replica crashes open a recovery
        # window, stalls wedge single dispatches, rpc errors reset
        # transports — all from one seeded plane
        # times= lifts crash's oneshot default: every crash opens a
        # CRASH_DOWN_S outage window, so each one fans out into many
        # per-request failovers. Rates are tuned so the 3-replica fleet
        # never has every replica down longer than the 5-attempt backoff
        # schedule can outlast — the phase asserts the recovery machinery
        # wins a WINNABLE fight; an unwinnable one (whole fleet dark for
        # seconds) is a capacity incident, not a failover test.
        spec = "crash:prob=0.03,times=5;stall:prob=0.04;rpc_error:prob=0.05"
        if ship_faults:
            # ISSUE 16 chaos leg: half the block ships fail before the
            # fetch (the runner's kv_ship_error hook) — every one must
            # degrade to re-prefill, never to a client-visible failure
            spec += ";kv_ship_error:prob=0.5"
        plane = FaultPlane(parse_spec(spec), seed=1994)
        kv_counts = {"resumes": 0, "fallbacks": 0}
        down_until: dict[str, float] = {}
        # backoff deliberately deterministic (jitter=0) and big enough
        # (50 ms base) that recovery time is dominated by the schedule,
        # not host sleep noise — the p95 is guarded across rounds
        # 6 attempts (was 5): the ISSUE 16 chaos leg adds kv_ship_error
        # on top of the crash/stall/rpc plan, and a failed ship's
        # re-prefill keeps the retry in flight longer — one more rung on
        # the schedule keeps the fight winnable without stretching the
        # (guarded) recovery tail of requests that win earlier
        cfg = RouterConfig(default_replica_inflight=8,
                           max_queue_depth=10000, max_queue_wait_s=10.0,
                           failover_max_attempts=6,
                           failover_backoff_base_s=0.05,
                           failover_backoff_max_s=0.2)
        router = FleetRouter(cfg, MemoryStore(), FakeFleet(N_REPLICAS))
        stub = Stub(stub_id="s", name="s", workspace_id="w",
                    config=StubConfig(timeout_s=30.0))
        injected = {"crash": 0, "stall": 0, "rpc_error": 0}

        def forward_for(avoid):
            async def forward(prefer):
                # the buffer's avoid semantics (gateway failover): failed
                # replicas deprioritized unless nothing else exists
                cands = [c for c in (prefer or ["r0"])
                         if c not in avoid] or list(prefer or ["r0"])
                cid = cands[0]
                now = time.monotonic()
                if down_until.get(cid, 0.0) > now:
                    # replica still restarting: connect refused
                    return ForwardResult(
                        status=502, body=b'{"error":"ConnectRefused"}',
                        container_id=cid)
                if plane.fire("crash"):
                    injected["crash"] += 1
                    down_until[cid] = now + CRASH_DOWN_S
                    return ForwardResult(
                        status=500,
                        body=b'{"error":"engine failure: induced"}',
                        container_id=cid)
                if plane.fire("rpc_error"):
                    injected["rpc_error"] += 1
                    return ForwardResult(
                        status=502,
                        body=b'{"error":"ConnectionResetError"}',
                        container_id=cid)
                svc = SERVICE_MS / 1000.0
                if avoid:
                    # failover retry: the survivor rebuilds the victim's
                    # KV — adopt shipped blocks when the ship lands,
                    # re-prefill the watermark when it doesn't (kv ship
                    # disabled, or the kv_ship_error fault fired)
                    if kv_ship and not plane.fire("kv_ship_error"):
                        kv_counts["resumes"] += 1
                        svc += ADOPT_S
                    else:
                        kv_counts["fallbacks"] += 1
                        svc += REPREFILL_S
                if plane.fire("stall"):
                    injected["stall"] += 1
                    svc += STALL_S    # wedged dispatch, then the
                    #                   watchdog-shaped 502
                    await asyncio.sleep(svc)
                    return ForwardResult(
                        status=502, body=b'{"error":"stream_gap"}',
                        container_id=cid)
                await asyncio.sleep(svc)
                return ForwardResult(status=200, body=b'{"ok":1}',
                                     container_id=cid)
            return forward

        recoveries: list[float] = []
        outcomes = {"ok": 0, "failed": 0, "failovers": 0}

        async def one(i: int) -> None:
            body = json.dumps({"tokens": [i % 7, i % 11, i % 13],
                               "max_new_tokens": 8}).encode()
            budget = sv.FailoverBudget(
                cfg.failover_max_attempts,
                BackoffPolicy(base_s=cfg.failover_backoff_base_s,
                              max_s=cfg.failover_backoff_max_s,
                              jitter=0.0))

            async def attempt(attempt, avoid):
                return await router.submit(stub, "chaos", body,
                                           forward_for(avoid))

            t_fail = [0.0]

            def on_failover(attempt, failed, delay):
                outcomes["failovers"] += 1
                if t_fail[0] == 0.0:
                    t_fail[0] = time.monotonic()

            res = await sv.submit_with_failover(attempt, budget,
                                                on_failover=on_failover)
            if res.status == 200:
                outcomes["ok"] += 1
                if t_fail[0]:
                    recoveries.append(time.monotonic() - t_fail[0])
            else:
                outcomes["failed"] += 1

        tasks = []
        for i in range(N_REQUESTS):
            tasks.append(asyncio.create_task(one(i)))
            await asyncio.sleep(STAGGER_MS / 1000.0)
        await asyncio.gather(*tasks)
        await router.stop()

        # ---- mid-stream watermark splice, same machinery the gateway
        # runs: a deterministic 'model' killed mid-generation, resumed
        # via prompt+delivered replay — the client sequence must equal
        # the unkilled reference exactly (no dup, no skip)
        def model_next(prefix):
            return (sum(prefix) * 31 + len(prefix)) % 997

        def serve(prompt, max_new, die_after=None):
            toks, prefix = [], list(prompt)
            for j in range(max_new):
                if die_after is not None and j >= die_after:
                    return toks, True
                t = model_next(prefix)
                toks.append(t)
                prefix.append(t)
            return toks, False

        splice_ok = 0
        splice_n = 16 if quick else 48
        for j in range(splice_n):
            prompt = [j + 1, (j * 3) % 17 + 1]
            max_new = 8 + (j % 9)
            die_after = 1 + (j % (max_new - 1)) if max_new > 1 else None
            reference, _ = serve(prompt, max_new)
            res = sv.StreamResumption(prompt, max_new,
                                      {"tokens": prompt,
                                       "max_new_tokens": max_new})
            got, died = serve(prompt, max_new, die_after=die_after)
            for t in got:
                res.note_token(t)
            body = json.loads(res.resume_payload())
            got2, _ = serve(body["tokens"], body["max_new_tokens"])
            for t in got2:
                res.note_token(t)
            if res.delivered == reference:
                splice_ok += 1

        recoveries.sort()

        def pct(p):
            if not recoveries:
                return 0.0
            return recoveries[min(int(len(recoveries) * p),
                                  len(recoveries) - 1)]

        return {"outcomes": outcomes, "injected": dict(injected),
                "kv": dict(kv_counts),
                "recovery_p50_s": round(pct(0.50), 4),
                "recovery_p95_s": round(pct(0.95), 4),
                "recovered": len(recoveries),
                "splice_ok": splice_ok, "splice_n": splice_n}

    # three legs, one seed (ISSUE 16): the headline leg recovers via
    # block-ship resume; the reprefill leg is the same chaos with kv
    # ship off (the improvement baseline); the chaos leg fault-injects
    # the ship itself (kv_ship_error) — every failed ship must degrade
    # to re-prefill with ZERO client-visible failures
    r = asyncio.run(run(kv_ship=True, ship_faults=False))
    r_off = asyncio.run(run(kv_ship=False, ship_faults=False))
    r_chaos = asyncio.run(run(kv_ship=True, ship_faults=True))
    out = {
        "faults_requests": N_REQUESTS,
        "faults_failed_requests": r["outcomes"]["failed"],
        "faults_failovers": r["outcomes"]["failovers"],
        "faults_recovered": r["recovered"],
        "faults_recovery_p50_s": r["recovery_p50_s"],
        "faults_recovery_p95_s": r["recovery_p95_s"],
        "faults_injected_crash": r["injected"]["crash"],
        "faults_injected_stall": r["injected"]["stall"],
        "faults_injected_rpc_error": r["injected"]["rpc_error"],
        "faults_stream_splice_ok": r["splice_ok"],
        "faults_stream_splice_n": r["splice_n"],
        "faults_kv_resumes": r["kv"]["resumes"],
        "faults_recovery_p95_reprefill_s": r_off["recovery_p95_s"],
        "faults_kv_fallbacks": r_chaos["kv"]["fallbacks"],
        "faults_kv_chaos_failed_requests": r_chaos["outcomes"]["failed"],
    }
    violations = []
    failed_total = (r["outcomes"]["failed"] + r_off["outcomes"]["failed"]
                    + r_chaos["outcomes"]["failed"])
    if failed_total > 0:
        violations.append(
            f"{failed_total} client-visible failed requests "
            "under induced faults (must be ZERO across all legs)")
    if r["outcomes"]["failovers"] == 0 or sum(r["injected"].values()) == 0:
        violations.append("no faults were actually induced — the chaos "
                          "phase measured nothing")
    if r["splice_ok"] != r["splice_n"]:
        violations.append(
            f"stream splice produced a duplicated/skipped token in "
            f"{r['splice_n'] - r['splice_ok']}/{r['splice_n']} resumes")
    if r["recovered"] == 0:
        violations.append("no request actually recovered via failover")
    if r["kv"]["resumes"] == 0:
        violations.append("no failover actually resumed via block ship")
    if r["recovered"] and r_off["recovered"] \
            and r["recovery_p95_s"] >= r_off["recovery_p95_s"]:
        violations.append(
            f"block-ship resume did not improve recovery p95 "
            f"({r['recovery_p95_s']}s vs re-prefill "
            f"{r_off['recovery_p95_s']}s)")
    if r_chaos["kv"]["fallbacks"] == 0:
        violations.append("kv_ship_error injected nothing — the "
                          "re-prefill fallback went unexercised")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: disaggregated prefill/decode + the KV wire format (ISSUE 16).
#
# Two legs:
#
# 1. kvwire roundtrip bit-exactness through the REAL pool machinery
#    (KvPool.export_blocks → import_blocks → re-export) on bf16 and
#    int8(+scale-plane) pools, plus the version gate. Judged HARD the
#    way quant parity is: a violation strips kvwire_roundtrip_exact
#    from the round, and bench_guard's HARD presence check fails the
#    stripped round.
#
# 2. TTFT p99 under a mixed long-doc / short-chat workload through the
#    REAL FleetRouter with the disagg policy on vs off. The replica
#    model is the continuous-batching interference disagg exists to
#    remove: prefills serialize per replica, and a prefill slows by
#    (1 + concurrent decodes) — so with disagg OFF, short chats queue
#    behind multi-hundred-ms long-doc prefills and long-doc prefills
#    crawl through decode-heavy replicas. Gates: disagg ON must WIN
#    long-doc p99 and never lose >2% short-chat p99.
# ---------------------------------------------------------------------------

def bench_disagg(quick: bool = False) -> dict:
    import asyncio

    import numpy as np

    out: dict = {}
    violations: list[str] = []

    # ---- leg 1: kvwire roundtrip bit-exactness ----------------------------
    import jax.numpy as jnp

    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving import kvwire
    from tpu9.serving.engine import EngineConfig
    from tpu9.serving.kvpool import KvPool
    from tpu9.serving.paged_kv import PrefixCache
    from tpu9.serving.shard import make_policy

    cfg = LLAMA_PRESETS["llama-tiny"]
    ecfg = EngineConfig(max_batch=2, max_seq_len=256,
                        prefill_buckets=(32, 64), decode_steps=(1, 4),
                        kv_block_size=32, kv_pool_blocks=16,
                        prefill_chunk=32, prefix_cache_blocks=8)
    rng = np.random.default_rng(7)
    exact = True
    payload = b""
    for kv_quant in (False, True):
        pool_a = KvPool(cfg, ecfg, kv_quant, make_policy(None))
        kv_a = pool_a.init_arrays()
        blocks = pool_a.alloc_blocks(3)
        idx = jnp.asarray(blocks, dtype=jnp.int32)
        for name in pool_a.wire_names():
            shape, dt = pool_a.array_shapes()[name]
            sub = (shape[0], len(blocks)) + tuple(shape[2:])
            vals = (rng.integers(-127, 128, size=sub, dtype=np.int8)
                    if np.dtype(dt) == np.dtype(np.int8)
                    else rng.standard_normal(sub).astype(np.float32))
            kv_a[name] = kv_a[name].at[:, idx].set(
                jnp.asarray(vals, dtype=dt))
        tokens = [(i * 7) % 211 + 1 for i in range(3 * 32)]
        t0 = time.perf_counter()
        payload = pool_a.export_blocks(
            kv_a, blocks, PrefixCache._key(tokens), len(tokens))
        t_exp = time.perf_counter() - t0
        pool_b = KvPool(cfg, ecfg, kv_quant, make_policy(None))
        kv_b = pool_b.init_arrays()
        t0 = time.perf_counter()
        kv_b, adopted, _ = pool_b.import_blocks(kv_b, payload)
        t_imp = time.perf_counter() - t0
        entry = pool_b.prefix_cache.acquire_for_export(tokens)
        back = b""
        if entry is not None:
            back = pool_b.export_blocks(kv_b, entry.blocks, entry.key,
                                        entry.n_tokens)
            pool_b.prefix_cache.release_pin(entry)
        which = "int8" if kv_quant else "bf16"
        if not (adopted and back == payload):
            exact = False
            violations.append(
                f"kvwire roundtrip not bit-exact ({which} pool)")
        out[f"kvwire_payload_kb_{which}"] = round(len(payload) / 1024, 2)
        out[f"kvwire_export_ms_{which}"] = round(t_exp * 1000, 3)
        out[f"kvwire_import_ms_{which}"] = round(t_imp * 1000, 3)
    # version gate: a bumped payload must refuse loudly, not misparse
    bumped = bytearray(payload)
    struct.pack_into("<H", bumped, 7, kvwire.FORMAT_VERSION + 1)
    try:
        kvwire.decode_header(bytes(bumped))
        exact = False
        violations.append("kvwire accepted an unknown format version")
    except kvwire.KvWireError:
        pass
    out["kvwire_roundtrip_exact"] = 1 if exact else 0

    # ---- leg 2: disagg routing, TTFT p99 on vs off ------------------------
    from tpu9.abstractions.common.buffer import ForwardResult
    from tpu9.config import RouterConfig
    from tpu9.router import FleetRouter
    from tpu9.statestore import MemoryStore
    from tpu9.types import ContainerState, ContainerStatus, Stub, StubConfig

    N_REPLICAS = 4
    N_REQUESTS = 160 if quick else 400
    STAGGER_MS = 4.0
    LONG_EVERY = 5                    # 20% long-doc, 80% short-chat
    LONG_PROMPT = 640                 # > disagg_prefill_tokens
    SHORT_PROMPT = 48
    PREFILL_S = {"long": 0.025, "short": 0.001}
    DECODE_S = {"long": 0.005, "short": 0.025}   # chats decode LONG

    class FakeFleet:
        def __init__(self, n):
            self.states = [ContainerState(
                container_id=f"r{i}", stub_id="s",
                status=ContainerStatus.RUNNING.value,
                address=f"127.0.0.1:{9200 + i}") for i in range(n)]

        async def containers_by_stub(self, stub_id, status=None):
            return list(self.states)

    async def run(disagg: bool) -> dict:
        cfg_r = RouterConfig(default_replica_inflight=8,
                             max_queue_depth=10000, max_queue_wait_s=30.0,
                             disagg_enabled=disagg,
                             disagg_prefill_tokens=512,
                             disagg_prefill_fraction=0.5)
        router = FleetRouter(cfg_r, MemoryStore(), FakeFleet(N_REPLICAS))
        stub = Stub(stub_id="s", name="s", workspace_id="w",
                    config=StubConfig(timeout_s=60.0))
        prefill_lock = {f"r{i}": asyncio.Lock() for i in range(N_REPLICAS)}
        decoding = {f"r{i}": 0 for i in range(N_REPLICAS)}
        # the deterministic partition _disagg_order computes: sorted ids,
        # first ceil(0.5 * 4) = 2 lean prefill
        prefill_part = {"r0", "r1"}
        ttft = {"long": [], "short": []}
        placed = {"long_on_prefill": 0, "long": 0}

        def forward_for(kind, t_start):
            async def forward(prefer):
                cid = (prefer or ["r0"])[0]
                if kind == "long":
                    placed["long"] += 1
                    placed["long_on_prefill"] += cid in prefill_part
                async with prefill_lock[cid]:
                    # continuous-batching interference: a prefill step
                    # shares the replica with every in-flight decode
                    slow = 1.0 + decoding[cid]
                    await asyncio.sleep(PREFILL_S[kind] * slow)
                ttft[kind].append(time.monotonic() - t_start)
                decoding[cid] += 1
                try:
                    await asyncio.sleep(DECODE_S[kind])
                finally:
                    decoding[cid] -= 1
                return ForwardResult(status=200, body=b'{"ok":1}',
                                     container_id=cid)
            return forward

        async def one(i: int) -> int:
            kind = "long" if i % LONG_EVERY == 0 else "short"
            n = LONG_PROMPT if kind == "long" else SHORT_PROMPT
            body = json.dumps({"tokens": [(i + j) % 251 + 1
                                          for j in range(n)],
                               "max_new_tokens":
                                   8 if kind == "long" else 128}).encode()
            res = await router.submit(stub, "mix", body,
                                      forward_for(kind, time.monotonic()))
            return res.status

        tasks = []
        for i in range(N_REQUESTS):
            tasks.append(asyncio.create_task(one(i)))
            await asyncio.sleep(STAGGER_MS / 1000.0)
        statuses = await asyncio.gather(*tasks)
        await router.stop()

        def p99(xs):
            xs = sorted(xs)
            return xs[min(int(len(xs) * 0.99), len(xs) - 1)] if xs else 0.0

        return {"long_p99_ms": round(p99(ttft["long"]) * 1000, 2),
                "short_p99_ms": round(p99(ttft["short"]) * 1000, 2),
                "failed": sum(1 for s in statuses if s != 200),
                "long_on_prefill_frac": round(
                    placed["long_on_prefill"] / max(1, placed["long"]), 3)}

    r_on = asyncio.run(run(disagg=True))
    r_off = asyncio.run(run(disagg=False))
    out.update({
        "disagg_longdoc_ttft_p99_ms_on": r_on["long_p99_ms"],
        "disagg_longdoc_ttft_p99_ms_off": r_off["long_p99_ms"],
        "disagg_shortchat_ttft_p99_ms_on": r_on["short_p99_ms"],
        "disagg_shortchat_ttft_p99_ms_off": r_off["short_p99_ms"],
        "disagg_longdoc_ttft_improvement": round(
            r_off["long_p99_ms"] / max(r_on["long_p99_ms"], 1e-6), 3),
        "disagg_shortchat_ttft_ratio": round(
            r_on["short_p99_ms"] / max(r_off["short_p99_ms"], 1e-6), 3),
        "disagg_long_on_prefill_frac": r_on["long_on_prefill_frac"],
    })
    if r_on["failed"] or r_off["failed"]:
        violations.append(f"disagg sim dropped requests "
                          f"(on={r_on['failed']}, off={r_off['failed']})")
    if out["disagg_longdoc_ttft_improvement"] <= 1.0:
        violations.append(
            "disagg ON did not win long-doc TTFT p99 "
            f"({r_on['long_p99_ms']}ms vs off {r_off['long_p99_ms']}ms)")
    if out["disagg_shortchat_ttft_ratio"] > 1.02:
        violations.append(
            "disagg ON lost >2% short-chat TTFT p99 "
            f"(ratio {out['disagg_shortchat_ttft_ratio']})")
    if r_on["long_on_prefill_frac"] < 0.8:
        violations.append(
            "disagg placement did nothing — only "
            f"{r_on['long_on_prefill_frac']:.0%} of long-doc prompts "
            "landed on the prefill partition")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: KV tiering + prefix directory (ISSUE 20) — two legs:
#   1. session-reuse routing under replica churn + a scale-to-zero/restore
#      round, directory+tiers ON vs affinity-only OFF, through the REAL
#      FleetRouter (the directory fold, promotion and adopt-hint paths are
#      the production code; only the serving replicas are simulated). The
#      prefix hit rate must be STRICTLY above the affinity baseline and
#      the modeled TTFT p95 no worse — the whole point of the tier ladder.
#   2. an eviction storm through the REAL KvPool, host tier on vs off:
#      down-paging must keep prefixes findable that the untiered pool
#      destroys, and one timed down/up-page cycle prices the paging path.
# ---------------------------------------------------------------------------


def bench_kvtier(quick: bool = False) -> dict:
    import asyncio

    out: dict = {}
    violations: list[str] = []

    from tpu9.abstractions.common.buffer import ForwardResult
    from tpu9.config import RouterConfig
    from tpu9.router import FleetRouter
    from tpu9.router.affinity import block_keys
    from tpu9.statestore import MemoryStore
    from tpu9.types import ContainerState, ContainerStatus, Stub, StubConfig

    BT = 16                               # affinity_block_tokens
    N_REPLICAS = 3
    N_SESSIONS = 12 if quick else 24
    TURNS = 4 if quick else 6
    CHURN_EVERY = 2                       # kill a replica every N turns
    PREFIX_BLOCKS = 12                    # 192-token session prefix
    BASE_MS = 1.0
    PREFILL_MS_PER_TOK = 0.02             # recompute price per token
    ADOPT_MS = 0.4                        # peer pull price (flat)

    class FakeFleet:
        def __init__(self, n):
            self.next_id = n
            self.states = [self._mk(i) for i in range(n)]

        @staticmethod
        def _mk(i):
            return ContainerState(
                container_id=f"r{i}", stub_id="s",
                status=ContainerStatus.RUNNING.value,
                address=f"127.0.0.1:{9300 + i}")

        def replace(self, cid: str) -> str:
            self.states = [st for st in self.states
                           if st.container_id != cid]
            st = self._mk(self.next_id)
            self.next_id += 1
            self.states.append(st)
            return st.container_id

        async def containers_by_stub(self, stub_id, status=None):
            return list(self.states)

    def session_tokens(s: int) -> list:
        return [(s * 131 + j * 7) % 251 + 1
                for j in range(PREFIX_BLOCKS * BT)]

    async def run(directory: bool) -> dict:
        import os
        os.environ.pop("TPU9_KV_TIER", None)
        cfg_r = RouterConfig(default_replica_inflight=8,
                             max_queue_depth=10000, max_queue_wait_s=30.0,
                             affinity_block_tokens=BT,
                             prefix_directory=directory)
        fleet = FakeFleet(N_REPLICAS)
        router = FleetRouter(cfg_r, MemoryStore(), fleet)
        if not directory:
            router.prefix_dir = None      # affinity-only baseline
        stub = Stub(stub_id="s", name="s", workspace_id="w",
                    config=StubConfig(timeout_s=60.0))
        # simulated replica prefix caches: cid -> {key_hex: n_tokens}
        caches: dict = {st.container_id: {} for st in fleet.states}
        key_hits: dict = {}               # (cid, key_hex) -> hit count
        ttft_ms: list = []
        hits = [0]
        total = [0]

        def heartbeat():
            """Fold each live replica's digest (and hot-key peer
            publications) into the directory — the pressure-beat path."""
            if router.prefix_dir is None:
                return
            for cid, cache in caches.items():
                stats = {"kvtier_keys": ",".join(
                    f"{k}:d:{n}" for k, n in cache.items())}
                peer = [k for k in cache
                        if key_hits.get((cid, k), 0) >= 2]
                if peer:
                    # digest == key in the sim's peer store
                    stats["kvtier_peer"] = ",".join(
                        f"{k}:{k}:{cache[k]}" for k in peer)
                router.prefix_dir.observe_replica(cid, stats)

        def forward_for(body: bytes, adopt):
            keys = [k.hex()[:16] for k in block_keys(body, BT)]
            nb_max = len(keys)

            async def forward(prefer):
                cid = (prefer or [fleet.states[0].container_id])[0]
                cache = caches.setdefault(cid, {})
                covered = 0
                for i, k in enumerate(keys):
                    if k in cache:
                        covered = (nb_max - i) * BT
                        key_hits[(cid, k)] = key_hits.get((cid, k), 0) + 1
                        break
                cost = BASE_MS
                if covered == 0 and adopt is not None:
                    # peer pull: the runner fetches kv:<digest> and the
                    # engine adopts — far cheaper than a full re-prefill
                    covered = adopt["n_tokens"]
                    cost += ADOPT_MS
                    cache[adopt["key"]] = adopt["n_tokens"]
                n_tok = len(json.loads(body)["tokens"])
                cost += PREFILL_MS_PER_TOK * max(0, n_tok - covered)
                hits[0] += covered > 0
                total[0] += 1
                ttft_ms.append(cost)
                for i, k in enumerate(keys):
                    cache[k] = (nb_max - i) * BT
                await asyncio.sleep(0.0005)
                return ForwardResult(status=200, body=b'{"ok":1}',
                                     container_id=cid)
            return forward

        in_peer = set()                   # sim peer store (key_hex)

        async def one(s: int, turn: int) -> int:
            toks = session_tokens(s) + [(turn * 13 + j) % 251 + 1
                                        for j in range(8)]
            body = json.dumps({"tokens": toks,
                               "max_new_tokens": 16}).encode()
            adopt = router.kv_adopt_hint(body)
            if adopt is not None and adopt["key"] not in in_peer:
                adopt = None              # stale hint: recompute path
            res = await router.submit(stub, "kv", body,
                                      forward_for(body, adopt))
            return res.status

        failed = 0
        for turn in range(TURNS):
            statuses = await asyncio.gather(
                *[one(s, turn) for s in range(N_SESSIONS)])
            failed += sum(1 for st in statuses if st != 200)
            heartbeat()
            if turn % CHURN_EVERY == CHURN_EVERY - 1:
                # replica death: hot keys were already peer-published on
                # the beat; claims die with the replica
                victim = fleet.states[0].container_id
                for k, n in caches.get(victim, {}).items():
                    if key_hits.get((victim, k), 0) >= 2:
                        in_peer.add(k)
                caches.pop(victim, None)
                newb = fleet.replace(victim)
                caches[newb] = {}
                router.note_dispatch_failure(victim)
        # scale-to-zero: every replica dies, fresh fleet restores; only
        # the peer tier (directory survivors + adopt hints) carries state
        for st in list(fleet.states):
            cid = st.container_id
            for k in caches.get(cid, {}):
                if key_hits.get((cid, k), 0) >= 2:
                    in_peer.add(k)
            caches.pop(cid, None)
            newb = fleet.replace(cid)
            caches[newb] = {}
            router.note_dispatch_failure(cid)
        statuses = await asyncio.gather(
            *[one(s, TURNS) for s in range(N_SESSIONS)])
        failed += sum(1 for st in statuses if st != 200)
        await router.stop()

        xs = sorted(ttft_ms)
        p95 = xs[min(int(len(xs) * 0.95), len(xs) - 1)] if xs else 0.0
        return {"hit_rate": round(hits[0] / max(1, total[0]), 4),
                "ttft_p95_ms": round(p95, 3), "failed": failed}

    r_on = asyncio.run(run(directory=True))
    r_off = asyncio.run(run(directory=False))
    out.update({
        "kvtier_prefix_hit_rate": r_on["hit_rate"],
        "kvtier_affinity_hit_rate": r_off["hit_rate"],
        "kvtier_ttft_p95_ms_on": r_on["ttft_p95_ms"],
        "kvtier_ttft_p95_ms_off": r_off["ttft_p95_ms"],
        "kvtier_ttft_p95_ratio": round(
            r_on["ttft_p95_ms"] / max(r_off["ttft_p95_ms"], 1e-6), 4),
    })
    if r_on["failed"] or r_off["failed"]:
        violations.append(f"kvtier sim dropped requests "
                          f"(on={r_on['failed']}, off={r_off['failed']})")
    if r_on["hit_rate"] <= r_off["hit_rate"]:
        violations.append(
            "prefix directory + tiers did not beat the affinity-only hit "
            f"rate ({r_on['hit_rate']} vs {r_off['hit_rate']})")
    if out["kvtier_ttft_p95_ratio"] > 1.0:
        violations.append(
            "tiering-on TTFT p95 regressed vs affinity-only "
            f"(ratio {out['kvtier_ttft_p95_ratio']})")

    # ---- leg 2: eviction storm through the real pool, tier on vs off ------
    import numpy as np

    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.engine import EngineConfig
    from tpu9.serving.kvpool import KvPool
    from tpu9.serving.paged_kv import PrefixCache
    from tpu9.serving.shard import make_policy

    cfg = LLAMA_PRESETS["llama-tiny"]
    ecfg = EngineConfig(max_batch=2, max_seq_len=256,
                        prefill_buckets=(32, 64), decode_steps=(1, 4),
                        kv_block_size=32, kv_pool_blocks=16,
                        prefill_chunk=32, prefix_cache_blocks=12)
    N_PREFIXES = 10 if quick else 20

    def storm(host_mb: int) -> float:
        pool = KvPool(cfg, ecfg, False, make_policy(None),
                      host_pool_mb=host_mb)
        kv = pool.init_arrays()
        inserted = []
        for i in range(N_PREFIXES):
            blocks = pool.alloc_blocks(2)
            tokens = [(i * 97 + j) % 241 + 1 for j in range(2 * 32)]
            pool.prefix_cache.insert(tokens, blocks)
            pool.allocator.release(blocks)
            inserted.append(PrefixCache._key(tokens))
            if pool.allocator.free_count < 6:
                if pool.tiered:
                    for e in pool.prefix_cache.spill_candidates(2):
                        pool.downpage(kv, e)
                pool.prefix_cache.evict_for_space(4)
        alive = sum(pool.prefix_cache.contains(k) for k in inserted)
        return alive / N_PREFIXES

    out["kvtier_storm_survival_on"] = round(storm(64), 4)
    out["kvtier_storm_survival_off"] = round(storm(0), 4)
    if out["kvtier_storm_survival_on"] <= out["kvtier_storm_survival_off"]:
        violations.append(
            "host tier did not improve eviction-storm prefix survival "
            f"({out['kvtier_storm_survival_on']} vs "
            f"{out['kvtier_storm_survival_off']})")

    # one timed down/up-page cycle prices the paging path (bit-exactness
    # is the test suite's job; the bench reports the device-sync cost)
    pool = KvPool(cfg, ecfg, False, make_policy(None), host_pool_mb=64)
    kv = pool.init_arrays()
    blocks = pool.alloc_blocks(3)
    tokens = [(j * 7) % 211 + 1 for j in range(3 * 32)]
    pool.prefix_cache.insert(tokens, blocks)
    pool.allocator.release(blocks)
    entry = pool.prefix_cache._entries[PrefixCache._key(tokens)]
    t0 = time.perf_counter()
    ok_down = pool.downpage(kv, entry)
    t_down = time.perf_counter() - t0
    t0 = time.perf_counter()
    planes = pool.uppage_planes(entry)
    kv = pool.complete_uppage(kv, entry, planes)
    np.asarray(kv[pool.wire_names()[0]])  # land the scatter
    t_up = time.perf_counter() - t0
    if not ok_down:
        violations.append("kvtier pricing cycle failed to down-page")
    out["kvtier_downpage_ms"] = round(t_down * 1000, 3)
    out["kvtier_uppage_ms"] = round(t_up * 1000, 3)

    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: speculative decoding (ISSUE 5) — tokens/sec spec-on vs spec-off
# through the REAL serving engine on two workloads: repetitive/code-like
# generations (prompt-lookup drafts must WIN) and random-token prompts
# (the acceptance-EWMA auto-disable must hold the regression under 5%).
# Greedy parity between the two engines is asserted on every request —
# a throughput win from wrong tokens is not a win.
# ---------------------------------------------------------------------------

def bench_spec(quick: bool = False) -> dict:
    import asyncio
    import random as _random

    from tpu9.serving.presets import load_engine
    from tpu9.utils import on_tpu

    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", XLA_CACHE_DIR)

    tpu = on_tpu()
    if tpu and not quick:
        # reachable only from a STANDALONE `bench.py --phase spec` on a
        # chip host (no --cpu): the orchestrator always forces this phase
        # CPU so the regression gate stays deterministic and the chip
        # goes to the llm/llm_endpoint/kernels phases
        settings = dict(preset="llama3-8b-int8", batch=8, max_seq=2048,
                        spec_len=8, requests=8, rep_new=256, adv_new=128,
                        passes=2, adv_passes=3, prefill_buckets=(128,),
                        decode_steps=(1, 8, 32))
    else:
        # passes: per-pass ratio noise on a shared CPU is ~±10%; the gate
        # reads the MEDIAN of paired per-pass ratios. The adversarial
        # ratio sits near 1.0 with a 0.95 gate — it gets more, shorter
        # passes so its median cannot flake below the gate on noise alone
        settings = dict(preset="llama-tiny", batch=4, max_seq=512,
                        spec_len=8, requests=4 if quick else 8,
                        rep_new=240 if quick else 400,
                        adv_new=96,
                        passes=2 if quick else 5,
                        adv_passes=3 if quick else 9,
                        prefill_buckets=(32, 64), decode_steps=(1, 4, 8))
    s = settings
    out: dict = {"spec_model": s["preset"], "spec_len": s["spec_len"],
                 "on_tpu": tpu}
    violations: list[str] = []

    # Repetitive workload: prompts whose GREEDY TRAJECTORY is genuinely
    # repetitive — found by an offline cycle search over seed prompts
    # (the random-weight bench model, like a real LLM on code/tables/
    # quoting traffic, drifts into short cycles for some contexts; these
    # seeds reach theirs within the first ~100 tokens). This is the
    # regime prompt-lookup speculation exists for. Adversarial workload:
    # uniform-random token prompts — nothing for the proposer to find,
    # the EWMA gate must keep verify compute off the hot path.
    rep_seeds = (487, 239, 232, 280, 52, 457, 404, 84)[:s["requests"]]
    rep_prompts = [[sd % 500 + 1, (sd * 7) % 500 + 1, (sd * 13) % 500 + 1]
                   * 3 for sd in rep_seeds]
    rng = _random.Random(5)
    adv_prompts = [[rng.randrange(1, 500) for _ in range(48)]
                   for _ in range(s["requests"])]

    def build(spec_len: int):
        eng = load_engine(s["preset"], max_batch=s["batch"],
                          max_seq_len=s["max_seq"],
                          prefill_buckets=s["prefill_buckets"],
                          decode_steps=s["decode_steps"],
                          spec_len=spec_len)
        eng.warmup()
        return eng

    async def one_pass(eng, prompts, max_new):
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            eng.generate(list(p), max_new_tokens=max_new)
            for p in prompts])
        return sum(len(o) for o in outs) / (time.perf_counter() - t0), outs

    async def run() -> dict:
        res: dict = {}
        for name, prompts, max_new, passes in (
                ("repetitive", rep_prompts, s["rep_new"], s["passes"]),
                ("adversarial", adv_prompts, s["adv_new"],
                 s["adv_passes"])):
            off, on = build(0), build(s["spec_len"])
            await off.start()
            await on.start()
            for eng in (off, on):     # untimed admission/graph warm pass
                await asyncio.gather(*[
                    eng.generate(list(p), max_new_tokens=8)
                    for p in prompts])
            # PAIRED passes: each pass times off then on back-to-back and
            # the gate reads the median of per-pass ratios — host noise
            # (turbo, page cache, neighbors) drifts on seconds timescales
            # and unpaired comparisons drown a 1.1-1.3x effect in it
            ratios, offs_t, ons_t = [], [], []
            outs_off = outs_on = None
            for _ in range(passes):
                tps_off, outs_off = await one_pass(off, prompts, max_new)
                tps_on, outs_on = await one_pass(on, prompts, max_new)
                offs_t.append(tps_off)
                ons_t.append(tps_on)
                ratios.append(tps_on / tps_off)
            st = on.stats()
            await off.stop()
            await on.stop()
            res[f"spec_tokens_per_sec_off_{name}"] = round(
                statistics.median(offs_t), 1)
            res[f"spec_tokens_per_sec_on_{name}"] = round(
                statistics.median(ons_t), 1)
            res[f"spec_ratio_{name}"] = round(statistics.median(ratios), 4)
            res[f"spec_acceptance_rate_{name}"] = round(
                st["spec_acceptance_rate"], 4)
            res[f"spec_windows_{name}"] = st["spec_windows"]
            # greedy-parity evidence. Exact token-for-token parity is the
            # f32 unit tests' gate (tests/test_spec_decode.py): at bf16,
            # random-weight logits carry exact and near (1-ulp) TIES
            # whose argmax can break differently between the decode and
            # verify graph shapes — a rare tie then forks the whole
            # downstream stream. So each fork is judged against the
            # full-context forward ORACLE: the spec-emitted token must be
            # within bf16 noise of the oracle's best logit, else it is a
            # verify/rollback bug, not a tie.
            import jax.numpy as _jnp

            from tpu9.models.transformer import decoder_forward
            from tpu9.serving.presets import build_params
            oracle_params, oracle_cfg = build_params(s["preset"])
            first_div = None
            for a, b, p in zip(outs_off, outs_on, prompts):
                if len(a) != len(b):
                    violations.append(
                        f"spec: output LENGTHS diverge on {name}")
                    break
                i = next((i for i, (x, y) in enumerate(zip(a, b))
                          if x != y), None)
                if i is None:
                    continue
                first_div = i if first_div is None else min(first_div, i)
                logits = decoder_forward(
                    oracle_params, _jnp.asarray([list(p) + a[:i]],
                                                _jnp.int32),
                    oracle_cfg)[0, -1]
                margin = float(_jnp.max(logits) - logits[b[i]])
                if margin > 0.05:           # far past bf16 rounding noise
                    violations.append(
                        f"spec: stream forks at token {i} on {name} and "
                        f"the spec token is {margin:.3f} below the "
                        "oracle argmax — verify/rollback bug, not a tie")
            res[f"spec_first_divergence_{name}"] = (
                -1 if first_div is None else first_div)
        return res

    out.update(asyncio.run(run()))
    out["spec_uplift_repetitive"] = out["spec_ratio_repetitive"]
    out["spec_adversarial_ratio"] = out["spec_ratio_adversarial"]
    if out["spec_uplift_repetitive"] < 1.0:
        violations.append(
            f"spec: repetitive workload ratio "
            f"{out['spec_uplift_repetitive']} < 1.0 — speculation does "
            "not pay for its verify compute where it should win")
    if out["spec_adversarial_ratio"] < 0.95:
        violations.append(
            f"spec: adversarial workload ratio "
            f"{out['spec_adversarial_ratio']} < 0.95 — the acceptance-"
            "EWMA auto-disable is not containing the regression")
    if out["spec_acceptance_rate_repetitive"] <= \
            out["spec_acceptance_rate_adversarial"]:
        violations.append(
            "spec: repetitive acceptance not above adversarial — the "
            "proposer is not finding the structure the workload has")
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: quantized serving (ISSUE 6) — int8 weights + int8 paged KV vs bf16
# through the REAL serving engine, plus the two pure bytes-moved headlines:
# `.tpu9w` shard bytes (cold start / scale-out traffic) and KV-pool
# capacity at equal HBM (admission headroom). Output parity between the
# engines is judged with the spec phase's oracle-margin rule — a
# throughput win from wrong tokens is not a win.
# ---------------------------------------------------------------------------

def bench_quant(quick: bool = False) -> dict:
    import asyncio
    import tempfile

    import jax
    import jax.numpy as jnp

    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.models.transformer import decoder_forward
    from tpu9.ops.quant import quantize_decoder, quantized_bytes
    from tpu9.serving import weights as wfmt
    from tpu9.serving.engine import EngineConfig, InferenceEngine
    from tpu9.serving.feasibility import weight_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    from tpu9.serving.presets import resolve_preset
    from tpu9.utils import on_tpu

    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", XLA_CACHE_DIR)

    tpu = on_tpu()
    if tpu and not quick:
        # standalone on a chip host: the ~1B preset is the smallest config
        # where decode is genuinely HBM-bandwidth-bound AND the bf16
        # baseline still fits next to the quantized engine
        s = dict(preset="llama-1b", batch=8, max_seq=2048,
                 prefill_buckets=(128,), decode_steps=(1, 8, 32),
                 kv_block=256, requests=8, max_new=192, passes=2,
                 dtype=None, tps_gate=1.15)
    else:
        # CPU (the orchestrated/regression path): compute-bound, so the
        # HBM win physically cannot show — the tokens/sec gate here is
        # only a catastrophe floor; the byte/capacity headlines and the
        # parity judge are the CPU-verifiable contract. f32 activations
        # kill bf16 argmax-tie noise in the parity comparison.
        s = dict(preset="llama-tiny", batch=4, max_seq=512,
                 prefill_buckets=(32, 64), decode_steps=(1, 4, 8),
                 kv_block=32, requests=4, max_new=96 if quick else 160,
                 passes=2 if quick else 3, dtype=jnp.float32,
                 tps_gate=0.5)
    out: dict = {"quant_model": s["preset"], "on_tpu": tpu}
    violations: list[str] = []

    from dataclasses import replace as _replace
    cfg, _ = resolve_preset(s["preset"])
    if s["dtype"] is not None:
        cfg = _replace(cfg, dtype=s["dtype"])

    # -- headline 1: .tpu9w shard bytes (flagship arithmetic + measured) --
    # the flagship ratio comes from the EXACT abstract-tree byte counts
    # the feasibility gate uses (jax.eval_shape — nothing materializes);
    # the measured ratio writes real tiny shards through save_params to
    # prove the pipeline (quantize → v2 index → shards) delivers it.
    # Measurements use the preset's REAL dtype (bf16): the f32 override
    # below exists only so the parity comparison has no argmax-tie noise
    # — an f32 baseline would inflate the "measured" int8 win ~2x over
    # the bf16 deployment story the flagship numbers tell.
    cfg8b, _ = resolve_preset("llama3-8b")
    mcfg, _ = resolve_preset(s["preset"])
    out["quant_shard_bytes_ratio"] = round(
        weight_bytes(cfg8b, False) / weight_bytes(cfg8b, True), 4)
    mparams = init_decoder(jax.random.PRNGKey(0), mcfg)
    with tempfile.TemporaryDirectory() as td:
        di = wfmt.save_params(mparams, os.path.join(td, "d.tpu9w"))
        qi = wfmt.save_params(mparams, os.path.join(td, "q.tpu9w"),
                              quantize="int8")
        out["quant_shard_bytes_ratio_measured"] = round(
            di["total_bytes"] / qi["total_bytes"], 4)
        out["quant_shard_index_version"] = qi["version"]
    if out["quant_shard_bytes_ratio"] < 1.8:
        violations.append(
            f"quant: flagship shard-bytes ratio "
            f"{out['quant_shard_bytes_ratio']} < 1.8")
    if abs(quantized_bytes(quantize_decoder(mparams)) - qi["total_bytes"]) \
            > qi["total_bytes"] * 0.01:
        violations.append("quant: feasibility bytes disagree with the "
                          "shards actually written")
    del mparams

    # -- headline 2: KV-pool capacity at equal HBM ------------------------
    # flagship arithmetic from the SAME helper the engine's auto sizing
    # divides by; measured from two real engines' allocators below
    out["quant_kv_capacity_ratio"] = round(
        kv_block_bytes(cfg8b, 256, False)
        / kv_block_bytes(cfg8b, 256, True), 4)
    if out["quant_kv_capacity_ratio"] < 1.9:
        violations.append(
            f"quant: flagship KV capacity ratio "
            f"{out['quant_kv_capacity_ratio']} < 1.9")

    def build(params, bcfg, kv_quant: str, warm: bool = True):
        eng = InferenceEngine(params, bcfg, EngineConfig(
            max_batch=s["batch"], max_seq_len=s["max_seq"],
            prefill_buckets=s["prefill_buckets"],
            decode_steps=s["decode_steps"],
            kv_block_size=s["kv_block"], kv_pool_blocks=0,
            prefill_chunk=min(s["prefill_buckets"]),
            prefix_cache_blocks=s["max_seq"] // s["kv_block"],
            kv_quant=kv_quant))
        if warm:
            eng.warmup()
        return eng

    # measured capacity at the preset's REAL dtype: construction alone
    # sizes the pools — no warmup, no weights touched
    m_off = build({}, mcfg, "", warm=False)
    m_on = build({}, mcfg, "int8", warm=False)
    out["quant_kv_blocks_bf16"] = m_off.allocator.n_blocks - 1
    out["quant_kv_blocks_int8"] = m_on.allocator.n_blocks - 1
    out["quant_kv_capacity_ratio_measured"] = round(
        (m_on.allocator.n_blocks - 1) / (m_off.allocator.n_blocks - 1), 4)
    del m_off, m_on

    dense_params = init_decoder(jax.random.PRNGKey(0), cfg)
    quant_params = quantize_decoder(dense_params)
    del dense_params
    off = build(quant_params, cfg, "")
    on = build(quant_params, cfg, "int8")

    # -- tokens/sec + parity: paired passes through both engines ----------
    import random as _random
    rng = _random.Random(11)
    prompts = [[rng.randrange(1, 400) for _ in range(24)]
               for _ in range(s["requests"])]

    async def one_pass(eng):
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            eng.generate(list(p), max_new_tokens=s["max_new"])
            for p in prompts])
        return sum(len(o) for o in outs) / (time.perf_counter() - t0), outs

    async def run():
        await off.start()
        await on.start()
        for eng in (off, on):        # untimed admission/graph warm pass
            await asyncio.gather(*[
                eng.generate(list(p), max_new_tokens=8) for p in prompts])
        ratios, offs_t, ons_t = [], [], []
        outs_off = outs_on = None
        for _ in range(s["passes"]):
            tps_off, outs_off = await one_pass(off)
            tps_on, outs_on = await one_pass(on)
            offs_t.append(tps_off)
            ons_t.append(tps_on)
            ratios.append(tps_on / tps_off)
        await off.stop()
        await on.stop()
        return ratios, offs_t, ons_t, outs_off, outs_on

    ratios, offs_t, ons_t, outs_off, outs_on = asyncio.run(run())
    out["quant_tokens_per_sec_off"] = round(statistics.median(offs_t), 1)
    out["quant_tokens_per_sec_on"] = round(statistics.median(ons_t), 1)
    out["quant_tokens_per_sec_ratio"] = round(statistics.median(ratios), 4)
    if out["quant_tokens_per_sec_ratio"] < s["tps_gate"]:
        what = ("int8 not faster than bf16 on the bandwidth-bound preset"
                if tpu else "int8 pathologically slower on CPU")
        violations.append(
            f"quant: tokens/sec ratio {out['quant_tokens_per_sec_ratio']}"
            f" < {s['tps_gate']} — {what}")

    # -- parity judge (HARD gate): both engines share the same quantized
    # weights, so any divergence isolates int8-KV noise. At each stream's
    # first fork, the int8-KV engine's token must be within quantization
    # noise of the full-context oracle's argmax (same weights, exact KV)
    # — otherwise it is a pool-write/table bug, not noise.
    MARGIN = 0.35
    first_div = None
    margin_max = 0.0
    for a, b, p in zip(outs_off, outs_on, prompts):
        if len(a) != len(b):
            # per-stream continue, not break: the remaining streams'
            # margins are diagnostic evidence for the SAME round
            violations.append("quant: output LENGTHS diverge")
            continue
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is None:
            continue
        first_div = i if first_div is None else min(first_div, i)
        logits = decoder_forward(
            quant_params, jnp.asarray([list(p) + b[:i]], jnp.int32),
            cfg)[0, -1]
        margin = float(jnp.max(logits) - logits[b[i]])
        margin_max = max(margin_max, margin)
        if margin > MARGIN:
            violations.append(
                f"quant: stream forks at token {i} and the int8-KV token "
                f"is {margin:.3f} below the oracle argmax (gate {MARGIN})"
                " — KV write/dequant bug, not quantization noise")
    out["quant_parity_first_divergence"] = (
        -1 if first_div is None else first_div)
    out["quant_oracle_margin_max"] = round(margin_max, 4)

    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: observability overhead (ISSUE 8) — the full request-lifecycle
# instrumentation (per-request trace spans + flight recorder + latency
# histograms) priced against the REAL engine, two ways:
#
#   1. obs_overhead_frac — the ≤2% gate, deterministic everywhere: the
#      per-window and per-request instrumentation hooks are microbenched on
#      the live engine (tight loop, min-of-reps — scheduling noise only ADDS
#      time, so the min converges on the true cost) and multiplied by the
#      window/request rates measured in the same run. Wall-clock A/B cannot
#      resolve 2% on a shared CPU host (measured noise floor here: a NULL
#      on-vs-off comparison of two IDENTICAL configs swings ±10-15%), and
#      hiding that behind more passes would be flaky-evidence theater.
#   2. obs_tokens_per_sec_ratio — paired interleaved tokens/sec with
#      neighbor-averaged baselines (off,on,off,on,...,off), gated at ≥0.98
#      ONLY on a real TPU (device windows dominate there and the host-side
#      hooks overlap device compute); on CPU it is a catastrophe floor, the
#      same split the quant phase uses for its HBM-bound throughput gate.
#
# Plus a decomposition-sanity check that the per-phase spans actually tile
# the request (queue + prefill + decode ≈ e2e within tolerance) — a cheap
# recorder that records the wrong timeline is not telemetry.
# ---------------------------------------------------------------------------

def bench_obs(quick: bool = False) -> dict:
    import asyncio

    import numpy as _np

    from tpu9.observability.trace import new_trace_id, tracer
    from tpu9.serving.presets import load_engine
    from tpu9.utils import on_tpu

    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", XLA_CACHE_DIR)

    tpu = on_tpu()
    # mixed-length prompts, paged engine with prefix cache + spec off: the
    # common serving shape. `repeats` request-sets per timed measurement
    # stretch each sample past the host's scheduling-jitter timescale.
    s = dict(preset="llama-tiny", batch=4, max_seq=512,
             requests=4 if quick else 8, max_new=96 if quick else 160,
             passes=3 if quick else 5, repeats=2 if quick else 3,
             prefill_buckets=(32, 64), decode_steps=(1, 4, 8),
             wall_gate=0.98 if tpu else 0.5)
    out: dict = {"obs_model": s["preset"], "on_tpu": tpu}
    violations: list[str] = []

    prompts = [[(7 * i + j) % 490 + 1 for j in range(8 + 6 * i)]
               for i in range(s["requests"])]

    def build(obs_on: bool):
        eng = load_engine(s["preset"], max_batch=s["batch"],
                          max_seq_len=s["max_seq"],
                          prefill_buckets=s["prefill_buckets"],
                          decode_steps=s["decode_steps"],
                          kv_block_size=32, kv_pool_blocks=0,
                          flight_cap=256 if obs_on else 0)
        eng.warmup()
        return eng

    async def measure(eng, traced: bool):
        """(tokens/sec, seconds, windows dispatched, trace ids) over
        `repeats` sequential request-sets."""
        tids: list = []
        total = 0
        rec0 = eng.flight.recorded if eng.flight is not None else 0
        t0 = time.perf_counter()
        for _ in range(s["repeats"]):
            batch_tids = [new_trace_id() if traced else ""
                          for _ in prompts]
            outs = await asyncio.gather(*[
                eng.generate(list(p), max_new_tokens=s["max_new"],
                             trace=(tid, "root") if tid else None)
                for p, tid in zip(prompts, batch_tids)])
            total += sum(len(o) for o in outs)
            tids = batch_tids
        dt = time.perf_counter() - t0
        # windows = flight records minus the admit records (one/request)
        windows = 0
        if eng.flight is not None:
            windows = (eng.flight.recorded - rec0
                       - s["repeats"] * len(prompts))
        return total / dt, dt, windows, tids

    def _min_time_us(fn, iters: int, reps: int) -> float:
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e6

    def microbench_hooks(eng) -> tuple[float, float]:
        """(per-window, per-request) instrumentation cost in µs, driven
        through the REAL hook methods on the live engine — flight record
        + per-request decode-span accounting + histogram observes, with the
        metric reservoirs saturated to their steady-state (sorted-insert)
        cost by the iteration count itself."""
        from tpu9.serving.engine import _Request, _Window
        iters, reps = (400, 3) if quick else (1500, 5)
        trace = ("ab" * 16, "cd" * 8)

        def mk_reqs():
            reqs = []
            for i in range(s["batch"]):
                r = _Request(request_id=f"mb{i}", prompt=[1] * 16,
                             max_new_tokens=s["max_new"], trace=trace,
                             t_enqueue_mono=time.monotonic(),
                             t_enqueue_wall=time.time())
                r.span_id = "ef" * 8
                reqs.append(r)
            return tuple(reqs)

        reqs = mk_reqs()
        mask = _np.ones(s["batch"], dtype=bool)
        delivered = {i: max(s["decode_steps"]) for i in range(s["batch"])}

        def one_window():
            win = _Window(kind="decode", k=max(s["decode_steps"]),
                          toks=None, mask=mask, reqs=reqs)
            eng._obs_stamp_window(win)
            win.delivered = dict(delivered)
            eng._obs_window(win, time.monotonic())

        def one_request():
            r = _Request(request_id="mbr", prompt=[1] * 16,
                         max_new_tokens=s["max_new"], trace=trace,
                         t_enqueue_mono=time.monotonic(),
                         t_enqueue_wall=time.time())
            eng._obs_admit_start(r, time.monotonic(), time.time())
            eng._obs_admit_end(r, time.monotonic(), time.time(), 0)
            eng._obs_first_token(r)
            eng._obs_done(r)

        return (_min_time_us(one_window, iters, reps),
                _min_time_us(one_request, iters, reps))

    def microbench_fleet() -> tuple[float, float]:
        """(per-timeline-record, per-SLO-evaluation) cost in µs with the
        rings SATURATED to steady state (ISSUE 12): a full deque(maxlen)
        ring is the append cost the gateway actually pays, and the burn
        evaluator walks full fast/slow windows."""
        from tpu9.config import SloConfig
        from tpu9.observability.slo import SloEvaluator
        from tpu9.observability.timeline import TimelineStore
        iters, reps = (400, 3) if quick else (1500, 5)
        cfg = SloConfig()
        tl = TimelineStore(capacity=cfg.timeline_capacity)
        # saturate: every series the sampler records per stub/replica,
        # rings full, monotonic stamps fresh enough to land in windows
        for name in ("router.st.queue_depth", "router.st.shed_rate",
                     "router.st.pressure", "router.st.submitted_total",
                     "router.st.shed_total", "router.st.ttft_p95_s",
                     "router.st.queue_wait_p95_s",
                     "engine.c0.tokens_per_sec", "engine.c0.kv_blocks_free",
                     "engine.c0.spec_acceptance_rate"):
            for i in range(cfg.timeline_capacity + 8):
                tl.record(name, float(i))
        ev = SloEvaluator(tl, cfg.objectives, burn_alert=cfg.burn_alert)

        def one_record():
            tl.record("router.st.queue_depth", 3.0)

        def one_eval():
            ev.evaluate("st")

        return (_min_time_us(one_record, iters, reps),
                _min_time_us(one_eval, iters, reps))

    def microbench_health(eng) -> tuple[float, float]:
        """(per-watchdog-assess, per-HBM-sample) cost in µs (ISSUE 14):
        the watchdog classifies one stats dict per runner beat; the HBM
        watermark is one ``memory_stats()`` sweep over the submesh on the
        stats() read path — both heartbeat-cadence, never per token."""
        from tpu9.observability.health import EngineWatchdog
        iters, reps = (400, 3) if quick else (1500, 5)
        wd = EngineWatchdog()
        stats = eng.stats()       # the real scalar surface, frozen

        def one_assess():
            wd.assess(stats)

        def one_hbm():
            eng.policy.hbm_used_gb_per_chip()

        return (_min_time_us(one_assess, iters, reps),
                _min_time_us(one_hbm, iters, reps))

    def microbench_cache() -> tuple[float, float]:
        """(per-chunk exchange-accounting, per-heartbeat snapshot) cost in
        µs for the cache-plane hooks (ISSUE 13): ``_note_exchange`` runs
        once per verified peer chunk on the restore path, ``snapshot()``
        once per worker heartbeat. Priced with a realistic per-peer table
        (8 peers warm)."""
        from tpu9.cache.client import CacheClient
        from tpu9.cache.store import DiskStore
        iters, reps = (400, 3) if quick else (1500, 5)
        import tempfile
        client = CacheClient(DiskStore(os.path.join(
            tempfile.gettempdir(), "tpu9-bench", "obs-cache-mb")),
            peers=None)
        peers = [f"10.0.0.{i}:7400" for i in range(8)]
        for p in peers:
            client._note_exchange(p, 0.004, 4 << 20)   # warm the table

        k = [0]

        def one_account():
            client._note_exchange(peers[k[0] % 8], 0.004, 4 << 20)
            k[0] += 1

        def one_snapshot():
            client.snapshot()

        return (_min_time_us(one_account, iters, reps),
                _min_time_us(one_snapshot, iters, reps))

    def microbench_decisions() -> tuple[float, float]:
        """(per-record hot path, per-new-request index eviction) cost in
        µs for the decision ledger (ISSUE 19). Saturated to steady state:
        full global ring, request index at max_requests — the hot path is
        ring append + index append + metrics inc on an EXISTING chain;
        the eviction path adds the longest-idle scan paid once per fresh
        request id once the index is full."""
        from tpu9.observability.decisions import DecisionLedger, rej
        iters, reps = (400, 3) if quick else (1500, 5)
        led = DecisionLedger()
        for i in range(led.capacity + led.max_requests):
            led.record("placement", "dispatch", request_id=f"mb{i}",
                       chosen="c0", rejected=[rej("c1", "saturated")],
                       signals={"queue_depth": 3.0, "candidates": 2.0},
                       stub_id="st")

        k = [0]

        def one_record():
            led.record("placement", "dispatch",
                       request_id=f"mb{led.capacity + k[0] % 64}",
                       chosen="c0", rejected=[rej("c1", "saturated")],
                       signals={"queue_depth": 3.0, "candidates": 2.0},
                       stub_id="st")
            k[0] += 1

        j = [led.capacity + led.max_requests]

        def one_fresh():
            led.record("placement", "dispatch", request_id=f"mb{j[0]}",
                       chosen="c0", rejected=[rej("c1", "saturated")],
                       signals={"queue_depth": 3.0, "candidates": 2.0},
                       stub_id="st")
            j[0] += 1

        rec = _min_time_us(one_record, iters, reps)
        fresh = _min_time_us(one_fresh, iters, reps)
        return rec, max(fresh - rec, 0.0)

    def microbench_kvtier(eng) -> tuple[float, float, float]:
        """(per-window quota check, per-tier-event journal append,
        per-beat digest) cost in µs for the KV tiering plane (ISSUE 20).
        The quota check rides EVERY window boundary — tiered or not;
        the decision-journal append is bounded at the down-page quota
        (2 per boundary worst case); the top-48 digest is heartbeat-
        cadence host work."""
        import collections as _collections
        iters, reps = (400, 3) if quick else (1500, 5)
        quota = _min_time_us(eng.scheduler.downpage_quota, iters, reps)
        journal = _collections.deque(maxlen=256)
        rec_d = {"decision": "spill", "chosen": "host:deadbeefdeadbeef",
                 "signals": {"n_tokens": 64.0, "free_blocks": 3.0,
                             "downpage_s": 0.002}}
        append = _min_time_us(lambda: journal.append(dict(rec_d)),
                              iters, reps)
        digest = _min_time_us(eng.kvtier_digest, iters, reps)
        return quota, append, digest

    async def run() -> dict:
        res: dict = {}
        off, on = build(False), build(True)
        await off.start()
        await on.start()
        for eng in (off, on):         # untimed admission/graph warm pass
            await asyncio.gather(*[
                eng.generate(list(p), max_new_tokens=8) for p in prompts])

        # interleaved off,(on,off)* — each ON sample is ratioed against
        # the MEAN of its two neighboring OFF samples, cancelling linear
        # host drift to first order
        offs = [await measure(off, traced=False)]
        ons = []
        last_tids: list = []
        for _ in range(s["passes"]):
            m = await measure(on, traced=True)
            ons.append(m)
            last_tids = m[3]
            offs.append(await measure(off, traced=False))
        ratios = [ons[i][0] / ((offs[i][0] + offs[i + 1][0]) / 2)
                  for i in range(s["passes"])]
        flight = on.flight_records(limit=256)

        res["obs_tokens_per_sec_off"] = round(
            statistics.median([m[0] for m in offs]), 1)
        res["obs_tokens_per_sec_on"] = round(
            statistics.median([m[0] for m in ons]), 1)
        res["obs_tokens_per_sec_ratio"] = round(
            statistics.median(ratios), 4)

        # instrumentation evidence: the ON engine must actually have
        # produced the records the gates claim to price
        if not flight or "decode" not in {r["kind"] for r in flight}:
            violations.append("obs: flight recorder produced no decode "
                              "records — the ON side measured nothing")

        # decomposition sanity from the REAL span trees of the last ON
        # measurement: queue_wait + prefill + decode windows ≈ the request
        # span, per request. The one-window-in-flight overlap
        # double-counts a little and loop bookkeeping leaks a little, so
        # the gate brackets ≈1 generously — catching the real failure
        # modes (spans missing, anchors wrong, windows double-booked) not
        # scheduler jitter. MUST run before the microbench below, which
        # floods the process tracer ring.
        coverage = []
        for tid in last_tids:
            spans = tracer.export(trace_id=tid)
            req = [sp for sp in spans if sp["name"] == "engine.request"]
            if not req:
                violations.append(f"obs: no engine.request span for {tid}")
                continue
            d = req[0]["durationMs"]
            parts = sum(sp["durationMs"] for sp in spans
                        if sp["name"] in ("engine.queue_wait",
                                          "engine.prefill",
                                          "engine.decode"))
            if d > 0:
                coverage.append(parts / d)
        if coverage:
            cov = statistics.median(coverage)
            res["obs_decomposition_coverage"] = round(cov, 4)
            if not 0.5 <= cov <= 1.7:
                violations.append(
                    f"obs: queue+prefill+decode covers {cov:.2f} of the "
                    "request span (gate 0.5..1.7) — the per-phase spans "
                    "do not decompose e2e latency")
        else:
            violations.append("obs: no span coverage measured")

        # the ≤2% gate: microbenched hook cost × measured rates
        win_us, req_us = microbench_hooks(on)
        dur = statistics.median([m[1] for m in ons])
        windows_ps = statistics.median([m[2] for m in ons]) / dur
        requests_ps = s["repeats"] * len(prompts) / dur
        frac = (win_us * windows_ps + req_us * requests_ps) / 1e6
        # fleet evidence layer (ISSUE 12): the timeline sampler + burn
        # evaluator run at FIXED cadences, not per token — price them at
        # their worst per-replica rates (engine series each heartbeat,
        # router series + one evaluation each sampler tick) and fold
        # into the same ≤2% budget
        rec_us, eval_us = microbench_fleet()
        from tpu9.config import SloConfig as _SloCfg
        _slo = _SloCfg()
        heartbeat_series = 10          # engine series per replica beat
        tick_series = 14               # router+slo series per stub tick
        # cache-plane series per worker per observer tick (ISSUE 13):
        # tier counters + rates + pool + 8 warm peers × 3 series
        cache_series = 44
        records_ps = (heartbeat_series / 2.0   # runner beat cadence
                      + (tick_series + cache_series)
                      / _slo.sample_interval_s)
        evals_ps = 1.0 / _slo.sample_interval_s
        # cache accounting hooks (ISSUE 13): snapshot() runs on the
        # 5 s worker heartbeat; the per-chunk _note_exchange hook runs on
        # the RESTORE path, not the serve loop — priced against its own
        # budget below, not folded into serve-time overhead
        account_us, snap_us = microbench_cache()
        # decision ledger (ISSUE 19): admission + placement records on
        # every request (failover records only on faults), one eviction
        # scan per fresh request id at steady state, and autoscaler /
        # replan records at sampler cadence — all priced against the
        # same ≤2% serve-time budget
        dec_rec_us, dec_evict_us = microbench_decisions()
        dec_frac = ((dec_rec_us * 2.0 + dec_evict_us) * requests_ps
                    + dec_rec_us / _slo.sample_interval_s) / 1e6
        frac += dec_frac
        res["obs_decision_record_us"] = round(dec_rec_us, 3)
        res["obs_decision_evict_us"] = round(dec_evict_us, 3)
        res["obs_decision_frac"] = round(dec_frac, 6)
        # KV tiering (ISSUE 20): the down-page quota check rides every
        # window boundary; journal appends are bounded at the quota (2
        # per boundary); the heartbeat digest is per-beat host work —
        # all priced against the same ≤2% serve-time budget (the paging
        # gathers themselves are window-boundary device syncs, priced
        # as wall time by bench.py --phase kvtier, not serve-loop hooks)
        kvt_quota_us, kvt_journal_us, kvt_digest_us = microbench_kvtier(on)
        kvt_frac = ((kvt_quota_us + 2.0 * kvt_journal_us) * windows_ps
                    + kvt_digest_us / 2.0) / 1e6
        frac += kvt_frac
        res["obs_kvtier_quota_us"] = round(kvt_quota_us, 3)
        res["obs_kvtier_journal_us"] = round(kvt_journal_us, 3)
        res["obs_kvtier_digest_us"] = round(kvt_digest_us, 3)
        res["obs_kvtier_frac"] = round(kvt_frac, 6)
        if dec_rec_us > 8.0:
            violations.append(
                f"obs: decision ledger record costs {dec_rec_us:.1f}µs"
                " (gate 8µs, same bar as the cache exchange-accounting"
                " hook) — the admission/placement hot path grew a heavy"
                " ledger hook")
        # replica health plane (ISSUE 14): one watchdog assess + one HBM
        # memory_stats() sweep per runner beat (2 s), plus the health
        # timeline/gauge records the gateway adds per beat (priced at
        # the timeline record cost already measured above)
        assess_us, hbm_us = microbench_health(on)
        health_records = 8     # hbm_*/liveness/health series per beat
        sampler_frac = (rec_us * records_ps + eval_us * evals_ps
                        + snap_us / 5.0
                        + (assess_us + hbm_us
                           + rec_us * health_records) / 2.0) / 1e6
        frac += sampler_frac
        res["obs_health_assess_us"] = round(assess_us, 3)
        res["obs_hbm_sample_us"] = round(hbm_us, 3)
        res["obs_timeline_record_us"] = round(rec_us, 3)
        res["obs_slo_eval_us"] = round(eval_us, 2)
        res["obs_cache_account_us"] = round(account_us, 3)
        res["obs_cache_snapshot_us"] = round(snap_us, 2)
        # a 4 MiB chunk at 10 GB/s local NVMe is ~400 µs of transfer —
        # the per-chunk accounting must stay ≤2% of even that best case
        if account_us > 8.0:
            violations.append(
                f"obs: cache exchange accounting costs {account_us:.1f}µs"
                " per chunk (gate 8µs = 2% of a best-case 4 MiB local"
                " transfer) — the restore hot path grew a heavy hook")
        res["obs_sampler_frac"] = round(sampler_frac, 6)
        res["obs_instr_window_us"] = round(win_us, 2)
        res["obs_instr_request_us"] = round(req_us, 2)
        res["obs_windows_per_sec"] = round(windows_ps, 2)
        res["obs_overhead_frac"] = round(frac, 5)
        if frac > 0.02:
            violations.append(
                f"obs: instrumentation costs {frac:.2%} of serve time "
                f"({win_us:.1f}µs/window × {windows_ps:.0f} windows/s + "
                f"{req_us:.1f}µs/request) — over the 2% budget")

        await off.stop()
        await on.stop()
        return res

    out.update(asyncio.run(run()))
    ratio = out.get("obs_tokens_per_sec_ratio", 0.0)
    if ratio < s["wall_gate"]:
        violations.append(
            f"obs: paired tokens/sec ratio {ratio} < {s['wall_gate']}"
            + (" — tracing + flight recorder slow the TPU serve loop "
               "beyond the overhead budget" if tpu else
               " — catastrophe floor on a noise-bound CPU host (NULL "
               "A/B noise here is ±10-15%; the binding 2% gate is "
               "obs_overhead_frac)"))
    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# phase: mesh-sharded multi-chip serving (ISSUE 9) — the tp=2 sharded engine
# priced against the 1-chip engine it must not fork from:
#
#   1. multichip_per_chip_ratio — (tp=2 tokens/sec ÷ 2 chips) / 1-chip
#      tokens/sec. On a real slice this is the serving-economics gate
#      (spreading a model must buy throughput, not just capacity). On
#      forced-CPU virtual devices every "chip" shares the same host cores,
#      so tp=2 adds partitioning overhead over ZERO extra silicon — the
#      ratio is reported as evidence but the binding CPU gate is a
#      catastrophe floor on the TOTAL throughput ratio (the quant/obs
#      precedent for wins CPU physically cannot show).
#   2. parity judge (HARD): token-for-token vs the 1-chip engine at f32;
#      any fork is judged against the full-context oracle's argmax margin
#      (sharded reductions may reassociate; a table/layout bug may not).
#   3. planner-vs-actual: the topology planner prices per-chip weights from
#      feasibility's eval_shape arithmetic; this phase measures the bytes
#      ACTUALLY resident on one device after placement and fails if the
#      deploy gate's numbers do not describe the real layout. Plus the
#      flagship arithmetic: llama3-8b provably infeasible on one v5e chip,
#      planned onto 2x1 with the 1x1 rejection ledger populated.
#   4. MFU/MBU under sharding: per-chip decode physics of the tp=2 engine
#      (streamed bytes / FLOPs divide across the submesh; the ceiling is
#      per chip, so utilization stays comparable to the 1-chip engine).
# ---------------------------------------------------------------------------

def bench_multichip(quick: bool = False) -> dict:
    import asyncio
    from dataclasses import replace as _replace

    import jax
    import jax.numpy as jnp

    from tpu9.benchsuite.physics import decode_byte_counts, decode_physics
    from tpu9.models import init_decoder
    from tpu9.models.transformer import decoder_forward
    from tpu9.serving.engine import EngineConfig, InferenceEngine
    from tpu9.serving.feasibility import weight_bytes
    from tpu9.serving.presets import resolve_preset
    from tpu9.serving.shard import Topology, make_policy, plan_topology
    from tpu9.utils import on_tpu

    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", XLA_CACHE_DIR)

    tpu = on_tpu()
    n_dev = jax.device_count()
    out: dict = {"on_tpu": tpu, "multichip_devices": n_dev}
    violations: list[str] = []
    TP = 2
    if n_dev < TP:
        raise RuntimeError(
            f"multichip phase needs >= {TP} devices, have {n_dev} — run "
            "via bench.py --cpu (forces an 8-device virtual CPU mesh) or "
            "on a real slice")

    # -- flagship planner arithmetic (pure host math, deterministic) ------
    plan = plan_topology("llama3-8b", "v5e-8")
    out["multichip_plan_llama3_8b_v5e"] = str(plan.topology)
    if plan.topology != Topology(2, 1) or len(plan.rejected) != 1:
        violations.append(
            f"multichip: planner put llama3-8b/v5e-8 on {plan.topology} "
            f"with {len(plan.rejected)} rejections (expected 2x1 after "
            "rejecting exactly 1x1) — the feasibility pricing moved")

    # f32 kills bf16 argmax-tie noise in the parity judge (spec/quant
    # precedent); tiny preset so CPU passes stay in budget
    s = dict(preset="llama-tiny", batch=4, max_seq=512,
             prefill_buckets=(32, 64), decode_steps=(1, 4, 8), kv_block=32,
             requests=4, max_new=64 if quick else 128,
             passes=2 if quick else 3)
    out["multichip_model"] = s["preset"]
    cfg, _ = resolve_preset(s["preset"])
    cfg = _replace(cfg, dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), cfg)

    # -- paired engines: 1-chip vs tp=2 -----------------------------------
    pol2 = make_policy(f"{TP}x1")
    def build(policy):
        eng = InferenceEngine(params, cfg, EngineConfig(
            max_batch=s["batch"], max_seq_len=s["max_seq"],
            prefill_buckets=s["prefill_buckets"],
            decode_steps=s["decode_steps"], kv_block_size=s["kv_block"],
            kv_pool_blocks=0, prefill_chunk=min(s["prefill_buckets"]),
            prefix_cache_blocks=s["max_seq"] // s["kv_block"]),
            policy=policy)
        eng.warmup()
        return eng

    one = build(make_policy(None))
    two = build(pol2)
    st = two.stats()
    out["multichip_topology"] = (
        f"{st['topo_tp']}x{st['topo_fsdp']}")

    # -- planner-vs-actual per-chip weight bytes --------------------------
    # the deploy-gate contract: feasibility's per-chip pricing (total
    # eval_shape bytes ÷ n_chips) must describe what the ENGINE actually
    # leaves resident on each device — measured from the serving engine's
    # own param tree, so a placement regression (e.g. a constructor path
    # that skips the policy and serves replicated weights) fails here
    # rather than silently inflating every other number. Small
    # non-dividing leaves replicate, so "describe" = within tolerance,
    # and genuinely ~1/tp of the model.
    dev0 = pol2.devices()[0]
    actual = 0
    for leaf in jax.tree_util.tree_leaves(two.params):
        for sh in leaf.addressable_shards:
            if sh.device == dev0:
                actual += sh.data.nbytes
    total = weight_bytes(cfg, False)
    planned = total / TP
    out["multichip_weight_shard_ratio"] = round(actual / total, 4)
    out["multichip_planner_weight_err"] = round(
        abs(actual - planned) / planned, 4)
    if out["multichip_weight_shard_ratio"] > 0.75:
        violations.append(
            f"multichip: tp={TP} leaves "
            f"{out['multichip_weight_shard_ratio']:.0%} of the weights on "
            "one chip (gate 75%) — the engine is not actually sharding")
    if out["multichip_planner_weight_err"] > 0.30:
        violations.append(
            f"multichip: planner per-chip weight pricing is off by "
            f"{out['multichip_planner_weight_err']:.0%} vs resident bytes "
            "(gate 30%) — the feasibility gate no longer describes the "
            "real layout")
    hbm = pol2.hbm_used_gb_per_chip()
    if hbm > 0.0:       # real backend memory stats (TPU); 0.0 on CPU
        out["multichip_hbm_used_gb_per_chip"] = hbm

    import random as _random
    rng = _random.Random(13)
    prompts = [[rng.randrange(1, 400) for _ in range(24)]
               for _ in range(s["requests"])]

    async def one_pass(eng):
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            eng.generate(list(p), max_new_tokens=s["max_new"])
            for p in prompts])
        return sum(len(o) for o in outs) / (time.perf_counter() - t0), outs

    async def run():
        await one.start()
        await two.start()
        for eng in (one, two):       # untimed admission/graph warm pass
            await asyncio.gather(*[
                eng.generate(list(p), max_new_tokens=8) for p in prompts])
        ones_t, twos_t = [], []
        outs_one = outs_two = None
        for _ in range(s["passes"]):
            tps_one, outs_one = await one_pass(one)
            tps_two, outs_two = await one_pass(two)
            ones_t.append(tps_one)
            twos_t.append(tps_two)
        await one.stop()
        await two.stop()
        return ones_t, twos_t, outs_one, outs_two

    ones_t, twos_t, outs_one, outs_two = asyncio.run(run())
    tps_one = statistics.median(ones_t)
    tps_two = statistics.median(twos_t)
    out["multichip_tokens_per_sec_1chip"] = round(tps_one, 1)
    out["multichip_tokens_per_sec_tp2"] = round(tps_two, 1)
    out["multichip_total_ratio"] = round(tps_two / tps_one, 4)
    out["multichip_per_chip_ratio"] = round(tps_two / TP / tps_one, 4)
    if tpu and out["multichip_per_chip_ratio"] < 0.35:
        violations.append(
            f"multichip: per-chip tokens/sec ratio "
            f"{out['multichip_per_chip_ratio']} < 0.35 on a real slice — "
            "the sharding tax ate the submesh")
    if not tpu and out["multichip_total_ratio"] < 0.2:
        violations.append(
            f"multichip: tp={TP} total throughput is "
            f"{out['multichip_total_ratio']}x the 1-chip engine — below "
            "the CPU catastrophe floor 0.2 (virtual devices share the "
            "host's cores; per-chip economics only exist on real silicon)")

    # -- parity judge (HARD gate) -----------------------------------------
    # token-for-token at f32; at each stream's first fork the sharded
    # engine's token must be within the oracle-argmax margin (sharded
    # psum reassociation), else it is a layout/table bug, not noise
    MARGIN = 0.35
    first_div = None
    margin_max = 0.0
    for a, b, p in zip(outs_one, outs_two, prompts):
        if len(a) != len(b):
            violations.append("multichip: output LENGTHS diverge")
            continue
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is None:
            continue
        first_div = i if first_div is None else min(first_div, i)
        logits = decoder_forward(
            params, jnp.asarray([list(p) + b[:i]], jnp.int32), cfg)[0, -1]
        margin = float(jnp.max(logits) - logits[b[i]])
        margin_max = max(margin_max, margin)
        if margin > MARGIN:
            violations.append(
                f"multichip: stream forks at token {i} and the sharded "
                f"token is {margin:.3f} below the oracle argmax (gate "
                f"{MARGIN}) — sharded KV/table bug, not reassociation")
    out["multichip_parity_first_divergence"] = (
        -1 if first_div is None else first_div)
    out["multichip_oracle_margin_max"] = round(margin_max, 4)

    # -- per-chip decode physics under sharding ---------------------------
    # streamed weights, KV traffic and matmul FLOPs all divide across the
    # submesh (tp shards both weight matrices and the KV head axis), so
    # the per-CHIP ceiling ratio is the honest utilization figure
    counts = decode_byte_counts(two.params, cfg, s["batch"],
                                24 + s["max_new"] // 2)
    total_tokens = s["requests"] * s["max_new"] * 1.0
    steps = total_tokens / s["batch"]
    step_ms = (total_tokens / tps_two) / max(steps, 1e-9) * 1e3
    spec = _known_chip(jax.devices()[0].device_kind)
    phys = decode_physics(
        step_ms=step_ms, batch=s["batch"],
        streamed_bytes=counts["streamed_bytes"] // TP,
        kv_bytes_per_step=counts["kv_bytes_per_step"] // TP,
        matmul_params=counts["matmul_params"] // TP,
        attn_flops_per_step=counts["attn_flops_per_step"] / TP,
        spec=spec) if spec else {}
    out["multichip_physics"] = phys
    out["multichip_engine_mbu"] = phys.get("mbu")
    out["multichip_engine_mfu"] = phys.get("mfu")

    out["violations"] = violations
    out["valid"] = not violations
    return out


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def _run_phase(phase: str, quick: bool, cpu: bool) -> dict:
    """Run one phase in a fresh subprocess (own process group), parse the
    last JSON line, then kill the whole group so nothing leaks forward."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if quick:
        cmd.append("--quick")
    if cpu or phase in ("router", "spec", "quant", "obs", "multichip",
                        "faults", "disagg", "scaleout", "kvtier") \
            or (phase.startswith("coldstart") and phase != "coldstart_jax_tpu"):
        # the serving stack and its runner children must never dial the chip
        # — ALL cold-start stack phases, not just the original one (round-3
        # advisor finding: coldstart_native/coldstart_jax ran unguarded).
        # The router phase is a pure-asyncio simulation: always CPU.
        # coldstart_jax_tpu is the exception: like llm_endpoint it forces its
        # own parent CPU and only the runner container holds the chip.
        cmd.append("--cpu")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    # setsid'd runner containers leave the group AND reparent to init when
    # the phase dies, so pids must be snapshotted WHILE the phase is alive —
    # a post-exit walk from a dead pid finds nothing
    seen_pids: set[int] = set()
    deadline = time.monotonic() + PHASE_TIMEOUT_S[phase]
    timed_out = False
    while True:
        try:
            out, err = proc.communicate(timeout=2)
            break
        except subprocess.TimeoutExpired:
            seen_pids.update(_descendants(proc.pid))
            if time.monotonic() > deadline:
                timed_out = True
                _kill_group(proc, seen_pids)
                out, err = proc.communicate()
                break
    _kill_group(proc, seen_pids)
    if timed_out:
        return {f"{phase}_error": f"timeout after {PHASE_TIMEOUT_S[phase]}s",
                f"{phase}_stderr_tail": err[-500:] if err else ""}

    for line in reversed(out.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return {f"{phase}_error": f"no JSON (rc={proc.returncode})",
            f"{phase}_stderr_tail": (err or "")[-500:]}


def _descendants(root_pid: int) -> list[int]:
    """All live descendant pids of root_pid via /proc PPid chains. Needed
    because ProcessRuntime starts runner containers with os.setsid() — they
    leave the phase's process group, so killpg alone cannot reach them."""
    ppid_of: dict[int, int] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/status") as f:
                for line in f:
                    if line.startswith("PPid:"):
                        ppid_of[int(entry)] = int(line.split()[1])
                        break
        except OSError:
            continue
    out, frontier = [], {root_pid}
    while frontier:
        nxt = {pid for pid, ppid in ppid_of.items() if ppid in frontier}
        nxt -= set(out)
        out.extend(nxt)
        frontier = nxt
    return out


def _kill_group(proc: subprocess.Popen, extra_pids: set[int] = frozenset()) -> None:
    """SIGKILL the phase's process group plus every pid snapshotted while
    the phase was alive (setsid'd runner containers sit outside the group
    and reparent to init on phase death — the snapshot is the only handle)."""
    kids = set(_descendants(proc.pid)) | set(extra_pids)
    kids.discard(proc.pid)
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    for pid in kids:
        # snapshot pids may have died and been REUSED by unrelated
        # processes — only kill ones that are verifiably ours (runner
        # containers carry TPU9_* env)
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if b"TPU9_" not in f.read():
                    continue
            os.kill(pid, signal.SIGKILL)
        except (OSError, ProcessLookupError, PermissionError):
            continue


def _tpu_alive(timeout_s: float = 120.0) -> bool:
    """One cheap probe in a child (this parent never imports jax): does a
    fresh process come up on the ``tpu`` backend? The child has exited —
    and released the chip — before any phase starts."""
    code = "import jax; print('TPU9_PROBE', jax.devices()[0].platform)"
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                            text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        return "TPU9_PROBE tpu" in (out or "")
    except subprocess.TimeoutExpired:
        return False
    finally:
        _kill_group(proc)


def _merge_validated(extra: dict, phase: str, result: dict,
                     value_keys: tuple[str, ...]) -> None:
    """Merge a phase result, REMOVING its headline numbers if the phase's
    own evidence rejected them — BENCH must never carry an un-evidenced
    number (round-2 failure: a physically impossible tokens/sec shipped)."""
    result = dict(result)
    # per-phase valid/violations fold into the shared validation block —
    # left at top level they'd clobber each other across phases
    violations = result.pop("violations", [])
    result.pop("valid", None)
    if violations:
        for key in value_keys:
            result.pop(key, None)
        result[f"{phase}_rejected"] = "; ".join(violations)
    extra.setdefault("validation", {}).setdefault("violations", []) \
        .extend(violations)
    extra.update(result)


REPO_DIR = os.path.dirname(os.path.abspath(__file__))


def _persist(name: str, obj: dict) -> None:
    """Write evidence to a side file IN THE REPO — the driver's tail capture
    truncated round 3's single output line mid-JSON and the headline was
    lost (`BENCH_r03.json "parsed": null`). The final stdout line stays
    compact; everything else lives here."""
    try:
        with open(os.path.join(REPO_DIR, name), "w") as f:
            json.dump(obj, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError:
        pass


def _run_chip_phases(detail: dict, quick: bool, cpu: bool) -> None:
    """llm + llm_endpoint + kernels (+ the on-chip restore cold start). On
    the chip a phase that errors ends the bench: there is no CPU retry that
    could put an off-chip number where an on-chip one was asked for."""
    def chip_phase(phase: str) -> dict:
        res = _run_phase(phase, quick, cpu)
        if f"{phase}_error" in res and not cpu:
            raise SystemExit(f"bench: {phase} failed on the chip: "
                             f"{res[f'{phase}_error']}")
        return res

    _merge_validated(detail, "llm", chip_phase("llm"), (
        "raw_decode_tokens_per_sec", "engine_tokens_per_sec",
        "engine_tokens_per_sec_per_chip"))

    # the endpoint phase's PARENT forces itself CPU internally; the runner
    # container holds the chip (unless the whole bench is CPU-forced, which
    # --cpu → TPU9_BENCH_CPU=1 propagates into the subprocess)
    _merge_validated(detail, "llm_endpoint", chip_phase("llm_endpoint"), (
        "endpoint_tokens_per_sec", "endpoint_tokens_per_sec_per_chip"))

    kern = chip_phase("kernels")
    # pop the shared validation keys BEFORE prefixing so _merge_validated
    # sees them (round-3 advisor finding: 'valid' leaked as 'kernel_valid')
    kern_viol = kern.pop("violations", [])
    kern.pop("valid", None)
    kern = {f"kernel_{k}" if not k.startswith("kernel") else k: v
            for k, v in kern.items()}
    kern["violations"] = kern_viol
    _merge_validated(detail, "kernels", kern, ("kernel_flash_ms",
                                               "kernel_paged_ms",
                                               "kernel_blocktable_ms"))

    if not cpu:
        # the on-chip restore cold start (VERDICT r04 #1)
        cjt = chip_phase("coldstart_jax_tpu")
        # strip the percentile dict and first-invoke time too on rejection —
        # an off-chip number must not survive under ANY _tpu key
        _merge_validated(detail, "coldstart_jax_tpu", cjt,
                         ("cold_start_jax_restore_tpu_p50_s",
                          "cold_start_jax_restore_tpu",
                          "cold_start_jax_first_tpu_s"))


def orchestrate(quick: bool, cpu: bool) -> dict:
    detail: dict = {}

    if not cpu and not _tpu_alive():
        # asked for the chip and there is none: fail, never fall back
        raise SystemExit("bench: no TPU backend in this environment — the "
                         "chip phases cannot run (pass --cpu for a CPU-only "
                         "functional run)")
    # chip phases FIRST, while nothing else has touched the chip
    _run_chip_phases(detail, quick, cpu)

    # every remaining phase is forced-CPU
    for phase, keys in (
            ("router", ("router_ttft_p50_ms", "router_ttft_p99_ms",
                        "router_shed_rate", "router_prefix_hit_rate",
                        "router_kv_hit_rate")),
            # chaos phase (ISSUE 15): a violation (any failed request,
            # a broken splice, or a chaos run that induced nothing)
            # strips every headline — bench_guard HARD-fails the
            # vanished faults_recovery_p95_s
            ("faults", ("faults_failed_requests", "faults_failovers",
                        "faults_recovered", "faults_recovery_p50_s",
                        "faults_recovery_p95_s",
                        "faults_stream_splice_ok",
                        # block-ship resume (ISSUE 16): the re-prefill
                        # baseline it must beat, and proof the
                        # kv_ship_error fallback was exercised
                        "faults_kv_resumes", "faults_kv_fallbacks",
                        "faults_recovery_p95_reprefill_s")),
            # KV wire + disaggregated prefill/decode (ISSUE 16): a
            # roundtrip that is not bit-exact strips
            # kvwire_roundtrip_exact — bench_guard HARD-fails the
            # vanished field (the quant parity precedent)
            ("disagg", ("kvwire_roundtrip_exact",
                        "kvwire_payload_kb_bf16", "kvwire_payload_kb_int8",
                        "kvwire_export_ms_bf16", "kvwire_import_ms_bf16",
                        "kvwire_export_ms_int8", "kvwire_import_ms_int8",
                        "disagg_longdoc_ttft_p99_ms_on",
                        "disagg_longdoc_ttft_p99_ms_off",
                        "disagg_shortchat_ttft_p99_ms_on",
                        "disagg_shortchat_ttft_p99_ms_off",
                        "disagg_longdoc_ttft_improvement",
                        "disagg_shortchat_ttft_ratio",
                        "disagg_long_on_prefill_frac")),
            # KV tiering + prefix directory (ISSUE 20): a violation (a
            # hit rate not strictly above the affinity baseline, a TTFT
            # p95 regression, a storm the host tier did not soften, or
            # any dropped request) strips every headline — bench_guard
            # HARD-fails the vanished kvtier_prefix_hit_rate
            ("kvtier", ("kvtier_prefix_hit_rate",
                        "kvtier_affinity_hit_rate",
                        "kvtier_ttft_p95_ms_on",
                        "kvtier_ttft_p95_ms_off",
                        "kvtier_ttft_p95_ratio",
                        "kvtier_storm_survival_on",
                        "kvtier_storm_survival_off",
                        "kvtier_downpage_ms", "kvtier_uppage_ms")),
            # scale-out plane (ISSUE 17): a violation (linear source
            # bytes, a failed chaos restore, or an execute-while-scaling
            # leg that never admitted early) strips every headline —
            # bench_guard HARD-fails the vanished
            # scaleout_source_bytes_ratio
            ("scaleout", ("scaleout_bringup_ratio",
                          "scaleout_source_bytes_ratio",
                          "scaleout_tree_wall_s",
                          "scaleout_single_restore_s",
                          "scaleout_serial_total_s",
                          "scaleout_serial_speedup",
                          "scaleout_source_bytes_serial",
                          "scaleout_source_bytes_tree",
                          "scaleout_peer_bytes_tree",
                          "scaleout_nonseed_peer_bytes",
                          "scaleout_bytes_by_edge",
                          "scaleout_tree_edges",
                          "scaleout_tree_source_edges",
                          "scaleout_first_group_frac",
                          "scaleout_first_admit_before_complete",
                          "scaleout_partial_admitted",
                          "scaleout_unhinted_fenced",
                          "scaleout_chaos_restore_ok",
                          "scaleout_chaos_peer_errors",
                          "scaleout_chaos_source_bytes",
                          "scaleout_report")),
            ("spec", ("spec_uplift_repetitive", "spec_adversarial_ratio",
                      "spec_tokens_per_sec_on_repetitive",
                      "spec_tokens_per_sec_off_repetitive",
                      "spec_acceptance_rate_repetitive")),
            ("quant", ("quant_shard_bytes_ratio",
                       "quant_shard_bytes_ratio_measured",
                       "quant_kv_capacity_ratio",
                       "quant_kv_capacity_ratio_measured",
                       "quant_tokens_per_sec_ratio",
                       "quant_tokens_per_sec_on",
                       "quant_tokens_per_sec_off")),
            ("multichip", ("multichip_tokens_per_sec_1chip",
                           "multichip_tokens_per_sec_tp2",
                           "multichip_total_ratio",
                           "multichip_per_chip_ratio",
                           "multichip_weight_shard_ratio",
                           "multichip_planner_weight_err",
                           "multichip_engine_mbu",
                           "multichip_engine_mfu")),
            ("obs", ("obs_tokens_per_sec_ratio",
                     "obs_tokens_per_sec_on",
                     "obs_tokens_per_sec_off",
                     "obs_decomposition_coverage",
                     "obs_overhead_frac", "obs_instr_window_us",
                     "obs_instr_request_us", "obs_windows_per_sec",
                     # replica health plane (ISSUE 14): watchdog tick +
                     # HBM sampler, priced microbench×rate like every
                     # other hook inside the same ≤2% budget
                     "obs_health_assess_us", "obs_hbm_sample_us",
                     # decision ledger (ISSUE 19): the WHY-record hook
                     # on admission/placement/failover, priced at its
                     # measured request rate inside the same budget
                     "obs_decision_record_us", "obs_decision_evict_us",
                     "obs_decision_frac",
                     # KV tiering (ISSUE 20): quota check + decision
                     # journal + heartbeat digest, priced at window/
                     # beat rates inside the same budget
                     "obs_kvtier_quota_us", "obs_kvtier_journal_us",
                     "obs_kvtier_digest_us", "obs_kvtier_frac")),
            ("coldstart", ("cold_start_p50_s",)),
            ("coldstart_native", ("cold_start_native_p50_s",
                                  "cold_start_native_pull_p50_s")),
            ("coldstart_jax", ("cold_start_jax_restore_p50_s",)),
            ("coldstart_stream", ("cold_start_jax_restore_stream_p50_s",
                                  "cold_start_warm_pool_restore_p50_s",
                                  "cold_start_classic_restore_p50_s",
                                  "weight_stream_fetch_s",
                                  "weight_stream_put_s",
                                  "warm_pool_hit",
                                  # decomposition evidence (ISSUE 13):
                                  # stripped as a block when the traced
                                  # spans disagree with the measured
                                  # intervals (>10%)
                                  "coldstart_fetch_window_s",
                                  "coldstart_put_window_s",
                                  "coldstart_overlap_frac",
                                  "coldstart_plan_s",
                                  "coldstart_trace_disagreement",
                                  "coldstart_trace_decomposition",
                                  "coldstart_bytes_by_tier",
                                  "coldstart_bytes_by_edge",
                                  "coldstart_hedge"))):
        res = _run_phase(phase, quick, cpu)
        _merge_validated(detail, phase, res, keys)

    v = detail.get("validation", {"violations": []})
    v["ok"] = not v["violations"]
    detail["validation"] = v
    return detail



# compact-extra keys lifted verbatim from the full detail (VERDICT r03
# next-round #1a: the final line carries headline fields ONLY)
_COMPACT_KEYS = (
    "backend", "on_tpu", "device_kind", "model",
    "engine_tokens_per_sec_per_chip", "engine_served_proof_ok",
    "endpoint_tokens_per_sec_per_chip", "endpoint_served_proof_ok",
    "endpoint_container_on_tpu",
    "cold_start_p50_s", "cold_start_native_p50_s",
    "cold_start_native_pull_p50_s", "cold_start_jax_restore_p50_s",
    "cold_start_jax_restore_stream_p50_s",
    "cold_start_warm_pool_restore_p50_s", "warm_pool_hit",
    "weight_stream_fetch_s", "weight_stream_put_s",
    "cold_start_jax_restore_tpu_p50_s", "jax_restore_tpu_backend",
    "kernel_flash_ms", "kernel_paged_ms",
    "router_ttft_p50_ms", "router_ttft_p99_ms", "router_ttft_random_p50_ms",
    "router_shed_rate", "router_prefix_hit_rate", "router_kv_hit_rate",
    "router_kv_hit_rate_random",
    "spec_uplift_repetitive", "spec_adversarial_ratio",
    "spec_tokens_per_sec_on_repetitive", "spec_tokens_per_sec_off_repetitive",
    "spec_acceptance_rate_repetitive", "spec_acceptance_rate_adversarial",
    "faults_requests", "faults_failed_requests", "faults_failovers",
    "faults_recovered", "faults_recovery_p50_s", "faults_recovery_p95_s",
    "faults_injected_crash", "faults_injected_stall",
    "faults_injected_rpc_error", "faults_stream_splice_ok",
    "faults_stream_splice_n",
    "faults_kv_resumes", "faults_kv_fallbacks",
    "faults_recovery_p95_reprefill_s",
    "kvwire_roundtrip_exact",
    "disagg_longdoc_ttft_p99_ms_on", "disagg_longdoc_ttft_p99_ms_off",
    "disagg_shortchat_ttft_p99_ms_on", "disagg_shortchat_ttft_p99_ms_off",
    "disagg_longdoc_ttft_improvement", "disagg_shortchat_ttft_ratio",
    "disagg_long_on_prefill_frac",
    "quant_shard_bytes_ratio", "quant_shard_bytes_ratio_measured",
    "quant_kv_capacity_ratio", "quant_kv_capacity_ratio_measured",
    "quant_tokens_per_sec_ratio", "quant_tokens_per_sec_on",
    "quant_tokens_per_sec_off", "quant_parity_first_divergence",
    "quant_oracle_margin_max",
    "multichip_tokens_per_sec_1chip", "multichip_tokens_per_sec_tp2",
    "multichip_total_ratio", "multichip_per_chip_ratio",
    "multichip_weight_shard_ratio", "multichip_planner_weight_err",
    "multichip_plan_llama3_8b_v5e", "multichip_topology",
    "multichip_parity_first_divergence", "multichip_oracle_margin_max",
    "multichip_engine_mbu", "multichip_engine_mfu",
    # scale-out plane (ISSUE 17): the two bench_guard-gated headlines
    # MUST ride the compact line — the guard reads the round capture,
    # and a HARD field absent from every round is a gate that never
    # fires — plus the small scalars that make a round self-evident
    "scaleout_bringup_ratio", "scaleout_source_bytes_ratio",
    "scaleout_serial_speedup", "scaleout_tree_wall_s",
    "scaleout_single_restore_s", "scaleout_tree_source_edges",
    "scaleout_nonseed_peer_bytes", "scaleout_first_admit_before_complete",
    "scaleout_chaos_restore_ok", "scaleout_chaos_peer_errors",
    "scaleout_chaos_source_bytes",
)


def _mk_summary(detail: dict) -> dict:
    """Flat headline summary lifted from the full detail: compact keys
    plus the physics-ceiling ratios. ``engine_mbu``/``engine_mfu`` come
    straight from the LLM phase's measured engine physics — per-token
    weight+KV bytes and FLOPs derived from the DecoderConfig — and are
    significant-digit rounded upstream so a CPU run reports its real
    (tiny) ratio instead of a flat 0.0."""
    extra: dict = {}
    for k in _COMPACT_KEYS:
        if k in detail:
            extra[k] = detail[k]
    for phys_key, short in (("engine_physics", "engine"),
                            ("endpoint_physics", "endpoint")):
        p = detail.get(phys_key)
        if isinstance(p, dict):
            extra[f"{short}_mbu"] = p.get("mbu")
            extra[f"{short}_mfu"] = p.get("mfu")
    return extra


def compact_line(detail: dict) -> dict:
    """One SMALL JSON line for the driver: headline metric + a flat summary.
    Full evidence (physics blocks, timelines, per-trial data) goes to
    BENCH_DETAIL.json via _persist, never into stdout."""
    extra = _mk_summary(detail)
    v = detail.get("validation", {"violations": [], "ok": False})
    extra["validation_ok"] = v.get("ok", False)
    extra["violations_n"] = len(v.get("violations", []))
    extra["detail_file"] = "BENCH_DETAIL.json"

    tps = extra.get("endpoint_tokens_per_sec_per_chip")
    if tps and extra.get("endpoint_container_on_tpu") \
            and extra.get("endpoint_served_proof_ok"):
        # the north-star config #2: llama3-8b int8 through @endpoint on the
        # chip. No published reference number exists (BASELINE.json
        # published:{}), so vs_baseline is the fraction of the chip's
        # physics ceiling achieved (endpoint mbu) — honest and comparable.
        return {"metric": "endpoint_tokens_per_sec_per_chip", "value": tps,
                "unit": "tok/s/chip",
                "vs_baseline": extra.get("endpoint_mbu") or 0.0,
                "extra": extra}
    if "cold_start_p50_s" in extra:
        value = extra["cold_start_p50_s"]
        return {"metric": "cold_start_p50_s", "value": value, "unit": "s",
                "vs_baseline": round(1.0 / max(value, 1e-9), 3),
                "extra": extra}
    if "engine_tokens_per_sec_per_chip" in extra:
        return {"metric": "engine_tokens_per_sec_per_chip",
                "value": extra["engine_tokens_per_sec_per_chip"],
                "unit": "tok/s/chip", "vs_baseline": 0.0, "extra": extra}
    return {"metric": "bench_failed", "value": 0, "unit": "",
            "vs_baseline": 0.0, "extra": extra}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (local verification)")
    ap.add_argument("--phase",
                    choices=["llm", "llm_endpoint", "kernels", "coldstart",
                             "coldstart_native", "coldstart_jax",
                             "coldstart_jax_tpu", "coldstart_stream",
                             "router", "spec", "quant", "obs", "multichip",
                             "faults", "disagg", "scaleout", "kvtier"],
                    help="run one phase in-process (used by the orchestrator)")
    args = ap.parse_args()

    if args.cpu:
        # --cpu means force EVERYTHING CPU, including llm_endpoint's runner
        # container. Without --cpu, llm_endpoint still forces its own parent
        # process CPU internally while the container gets the chip.
        os.environ["TPU9_BENCH_CPU"] = "1"
        # llm_endpoint force_cpu()s itself; the router phase never imports
        # jax at all (pure asyncio simulation)
        if args.phase not in ("llm_endpoint", "router", "faults"):
            from tpu9.utils import force_cpu
            force_cpu(host_devices=0 if (args.phase or "")
                      .startswith("coldstart") else 8)

    if args.phase:
        fn = {"llm": bench_llm, "llm_endpoint": bench_llm_endpoint,
              "kernels": bench_kernels, "coldstart": bench_cold_start,
              "coldstart_native": bench_cold_start_native,
              "coldstart_jax": bench_cold_start_jax,
              "coldstart_jax_tpu": bench_cold_start_jax_tpu,
              "coldstart_stream": bench_cold_start_stream,
              "router": bench_router, "spec": bench_spec,
              "quant": bench_quant, "obs": bench_obs,
              "multichip": bench_multichip,
              "faults": bench_faults, "disagg": bench_disagg,
              "scaleout": bench_scaleout,
              "kvtier": bench_kvtier}[args.phase]
        try:
            print(json.dumps(fn(quick=args.quick)))
        except Exception as exc:   # noqa: BLE001 — phase errors are data
            import traceback
            traceback.print_exc()
            print(json.dumps(
                {f"{args.phase}_error": f"{type(exc).__name__}: {exc}"}))
            sys.exit(1)
        return

    detail = orchestrate(args.quick, args.cpu)
    _persist("BENCH_DETAIL.json", detail)
    line = compact_line(detail)
    print(json.dumps(line))
    if line["metric"] == "bench_failed":
        sys.exit(1)


if __name__ == "__main__":
    main()
