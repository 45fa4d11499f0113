"""Physics/anti-fooling validation for the benchmark harness.

VERDICT round-2 items #1/#10: BENCH must never again carry a number that
violates the chip's physical limits (73k tok/s/chip on a v5e implied 23 TB/s
of HBM bandwidth). These tests pin the validator's behavior: impossible
timings are rejected, plausible ones pass, and the accounting (bytes/step,
FLOPs/step) matches hand-computed values for known configs.
"""

import jax
import jax.numpy as jnp
import pytest

from tpu9.benchsuite.physics import (chip_spec, decode_byte_counts,
                                     decode_physics,
                                     linear_scaling_violations,
                                     matmul_physics, physics_violations)
from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.ops.quant import init_quantized_decoder, quantized_bytes


def test_chip_spec_lookup():
    v5e = chip_spec("TPU v5 lite")
    assert v5e.name == "tpu-v5e"
    assert v5e.hbm_gbps == 819.0
    assert chip_spec("TPU v4").name == "tpu-v4"
    # a device the table does not know is an error, never an invented peak
    with pytest.raises(KeyError, match="mystery accelerator"):
        chip_spec("mystery accelerator")


def test_round2_number_is_rejected():
    """The round-2 fiction: llama-1b (≈2.47 GB bf16 streamed),
    batch 8, 0.109 ms/step on a v5e ⇒ ~23 TB/s. Must be flagged."""
    spec = chip_spec("TPU v5 lite")
    phys = decode_physics(step_ms=0.109, batch=8,
                          streamed_bytes=2_470_000_000,
                          kv_bytes_per_step=0, matmul_params=1_240_000_000,
                          spec=spec)
    assert phys["mbu"] > 20            # ~28x the chip's bandwidth
    fails = physics_violations(phys, what="llm")
    assert fails and "did not fence" in fails[0]


def test_plausible_number_passes():
    """8B int8 (~8 GB streamed) at 17 ms/step on v5e ≈ 0.6 MBU — fine."""
    spec = chip_spec("TPU v5 lite")
    phys = decode_physics(step_ms=17.0, batch=8,
                          streamed_bytes=8_000_000_000,
                          kv_bytes_per_step=1_100_000_000,
                          matmul_params=8_000_000_000, spec=spec)
    assert 0.3 < phys["mbu"] < 1.0
    assert physics_violations(phys, what="llm") == []


def test_kernel_mfu_rejection():
    """Round 2's flash '0.029 ms' at [4,2048,16,128] ⇒ ~4.7 PFLOP/s on a
    197-TFLOP/s chip. Must be flagged."""
    spec = chip_spec("TPU v5 lite")
    b, t, h, d = 4, 2048, 16, 128
    rep = matmul_physics(elapsed_ms=0.029, flops=2.0 * b * t * t * h * d,
                         bytes_moved=4 * b * t * h * d * 2, spec=spec)
    assert rep["mfu"] > 5
    assert physics_violations(rep, what="flash")


def test_linear_scaling_detects_async_clock():
    # round-2 failure shape: 2x work "completes" in ~the same elapsed time
    assert linear_scaling_violations(0.007, 0.008, what="llm")
    assert linear_scaling_violations(0.10, 0.21, what="llm") == []
    assert linear_scaling_violations(0.0, 0.2, what="llm")


def test_decode_byte_counts_tiny_exact():
    cfg = LLAMA_PRESETS["llama-tiny"]
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    c = decode_byte_counts(params, cfg, batch=2, mean_ctx=64)
    # hand count: per layer wq 128*128, wk/wv 128*64 each, wo 128*128,
    # gate/up 128*256 each, down 256*128; 2 layers; lm_head 128*512
    per_layer = (128 * 128 * 2 + 128 * 64 * 2 + 3 * 128 * 256)
    expect_params = per_layer * 2 + 128 * 512
    assert c["matmul_params"] == expect_params
    # bf16: 2 bytes/param (+ norm vectors: 5 * 128 f32 = 2560 bytes)
    assert c["streamed_bytes"] == expect_params * 2 + 5 * 128 * 4
    # kv: 2(K,V) * L * B * ctx * KH*D * 2B  read + one-row write
    kv_read = 2 * 2 * 2 * 64 * (2 * 32) * 2
    kv_write = 2 * 2 * 2 * (2 * 32) * 2
    assert c["kv_bytes_per_step"] == kv_read + kv_write


def test_quantized_init_structure_and_size():
    cfg = LLAMA_PRESETS["llama-tiny"]
    qp = init_quantized_decoder(jax.random.PRNGKey(0), cfg)
    # same tree paths as the dense init
    dense = init_decoder(jax.random.PRNGKey(0), cfg)
    assert set(qp.keys()) == set(dense.keys())
    assert set(qp["layers"][0].keys()) == set(dense["layers"][0].keys())
    # projections are int8 entries
    assert qp["layers"][0]["wq"]["q"].dtype == jnp.int8
    assert qp["lm_head"]["q"].shape == (cfg.dim, cfg.vocab_size)
    # ~half the bytes of the bf16 tree (embed stays bf16)
    assert quantized_bytes(qp) < 0.75 * quantized_bytes(dense)


def test_quantized_init_serves_through_engine():
    """The int8-synthesized tree must run the full engine path (decode
    windows + sampling) — this is the flagship bench configuration at toy
    scale."""
    import asyncio

    from tpu9.serving.presets import load_engine

    async def run():
        engine = load_engine("llama-tiny-int8", max_batch=2, max_seq_len=64,
                             prefill_buckets=(16,), decode_steps=(1, 4))
        await engine.start()
        out = await engine.generate([3, 1, 4, 1, 5], max_new_tokens=6)
        out2 = await engine.generate([3, 1, 4, 1, 5], max_new_tokens=6)
        await engine.stop()
        return out, out2

    out, out2 = asyncio.run(run())
    assert len(out) == 6
    assert out == out2                 # greedy decode is deterministic


def test_int8_streamed_bytes_counted_at_int8_width():
    cfg = LLAMA_PRESETS["llama-tiny"]
    qp = init_quantized_decoder(jax.random.PRNGKey(0), cfg)
    dense = init_decoder(jax.random.PRNGKey(0), cfg)
    cq = decode_byte_counts(qp, cfg, batch=1, mean_ctx=8)
    cd = decode_byte_counts(dense, cfg, batch=1, mean_ctx=8)
    assert cq["matmul_params"] == cd["matmul_params"]
    assert cq["streamed_bytes"] < 0.75 * cd["streamed_bytes"]


def test_unknown_preset_raises():
    from tpu9.serving.presets import resolve_preset
    with pytest.raises(KeyError):
        resolve_preset("llama-nope")
    cfg, q = resolve_preset("llama3-8b-int8")
    assert q and cfg.n_layers == 32
