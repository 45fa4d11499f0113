"""What the TPU's compiler says about the serving kernels, asked without a
chip (``/opt/skills/guides/on-chip-measurement`` §2): each pallas kernel of
the llama3-8b path at its real widths, compiled for a DESCRIBED v5e device.
Interpret-mode tests cannot see what these see — tiling, VMEM budgets, and
(the case that was refused before PR 21) a Mosaic call inside a GSPMD program.
A compile that passes is not a chip run; ``chip_smoke.py`` is.
"""

import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import tpu9.ops.attention as attention_ops
from tpu9.ops.attention import flash_attention, paged_attention_dispatch
from tpu9.ops.paged_attention import (paged_decode_attention,
                                      paged_decode_attention_quant,
                                      ragged_decode_attention,
                                      xla_paged_decode_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# llama3-8b serving widths (ISSUE 21): batch 8, 32 q / 8 kv heads, head 128,
# KV block 128, 2048 context = a 16-column block table (+1 trash column)
B, QH, KH, D, BS, CTX = 8, 32, 8, 128, 128, 2048
MB = CTX // BS + 1
N_BLOCKS = B * (CTX // BS) + 1


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:    # noqa: BLE001 — no libtpu, or it cannot
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
    return topo.devices


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip — keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _paged_args(d, sharding, quant=False):
    """(q, k_pool, v_pool[, k_scale, v_scale], table, lens) shapes."""
    def s(shape, dt, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    pool = s((N_BLOCKS, BS, KH, d), pool_dt)
    scales = [s((N_BLOCKS, BS, KH), jnp.float32)] * 2 if quant else []
    return [s((B, 1, QH, d), jnp.bfloat16), pool, pool, *scales,
            s((B, MB), jnp.int32), s((B,), jnp.int32)]


def _kernel_case(name, d):
    """(callable, abstract args builder) for one single-chip kernel."""
    def dense(sharding, t):
        def s(shape):
            return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                        sharding=sharding)
        return s((B if t == 1 else 2, t, QH, d)), \
            s((B if t == 1 else 2, CTX, KH, d))
    if name == "flash":
        return flash_attention, lambda sh: (
            dense(sh, CTX)[0], dense(sh, CTX)[1], dense(sh, CTX)[1])
    if name == "ragged":
        return ragged_decode_attention, lambda sh: (
            dense(sh, 1)[0], dense(sh, 1)[1], dense(sh, 1)[1],
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh))
    if name == "paged":
        return paged_decode_attention, lambda sh: _paged_args(d, sh)
    return paged_decode_attention_quant, \
        lambda sh: _paged_args(d, sh, quant=True)


@pytest.mark.parametrize("name,head_dim", [
    ("flash", 128), ("ragged", 128), ("paged", 128), ("paged_int8", 128),
    # llama-1b: same head counts, head 64
    ("flash", 64), ("paged", 64), ("paged_int8", 64),
    # the mesh-sharded replica: the dispatcher's shard_map over a 4-chip
    # mesh with pool and q sharded on the head axis (refused before PR 21:
    # "Mosaic kernels cannot be automatically partitioned")
    ("paged_on_mesh", 128), ("paged_int8_on_mesh", 128),
])
def test_kernel_compiles_for_a_described_v5e(v5e, no_compile_cache,
                                             monkeypatch, name, head_dim):
    if name.endswith("_on_mesh"):
        # dispatch as on the chip: the sandbox's backend is the CPU
        monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
        mesh = Mesh(np.array(v5e).reshape(1, 1, 1, 4),
                    ("dp", "fsdp", "sp", "tp"))
        heads4 = NamedSharding(mesh, P(None, None, "tp", None))
        heads3 = NamedSharding(mesh, P(None, None, "tp"))
        rep = NamedSharding(mesh, P())
        args = _paged_args(head_dim, None, quant="int8" in name)
        args = [jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding={4: heads4, 3: heads3}.get(len(a.shape), rep))
            for a in args]
        if "int8" in name:
            def fn(q, k, v, ks, vs, table, lens):
                return paged_attention_dispatch(q, k, v, table, lens, ks,
                                                vs, mesh=mesh)
        else:
            def fn(q, k, v, table, lens):
                return paged_attention_dispatch(q, k, v, table, lens,
                                                mesh=mesh)
    else:
        from jax.sharding import SingleDeviceSharding
        fn, build = _kernel_case(name, head_dim)
        args = build(SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.endswith("_on_mesh"):
        # each chip holds a quarter of the k and v pools, and nothing
        # gathers them
        pools = 2 * N_BLOCKS * BS * KH * head_dim * args[1].dtype.itemsize
        assert compiled.memory_analysis().argument_size_in_bytes \
            < 0.3 * pools
        assert "all-gather" not in compiled.as_text()
        if "int8" not in name:
            # and why the wrapper exists: GSPMD alone refuses the kernel
            with pytest.raises(NotImplementedError, match="shard_map"):
                jax.jit(lambda q, k, v, t, n: paged_attention_dispatch(
                    q, k, v, t, n)).lower(*args).compile()


@pytest.mark.multichip
def test_paged_kernel_under_shard_map_matches_the_oracle():
    """The same shard_map wrapping, RUN: four virtual CPU devices, the
    kernel interpreted, against the XLA oracle on unsharded inputs."""
    import functools
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                ("dp", "fsdp", "sp", "tp"))
    b, qh, kh, d, bs, mb = 2, 8, 4, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, qh, d), jnp.float32)
    kp = jax.random.normal(ks[1], (b * mb + 1, bs, kh, d), jnp.float32)
    vp = jax.random.normal(ks[2], (b * mb + 1, bs, kh, d), jnp.float32)
    table = (jnp.arange(b * mb, dtype=jnp.int32) + 1).reshape(b, mb)
    lens = jnp.asarray([bs * mb, bs + 3], jnp.int32)
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    sharded = attention_ops._per_chip_heads(
        functools.partial(paged_decode_attention, interpret=True), mesh,
        (attention_ops._HEADS4, attention_ops._HEADS4,
         attention_ops._HEADS4, P(), P()))
    got = jax.jit(sharded)(jax.device_put(q, heads),
                           jax.device_put(kp, heads),
                           jax.device_put(vp, heads), table, lens)
    want = xla_paged_decode_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert got.sharding.spec == P(None, None, "tp", None)


def test_chip_smoke_refuses_a_machine_without_a_tpu():
    """``python chip_smoke.py`` off the chip: non-zero within seconds, and
    the success line is never printed."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"platform": "cpu"' in proc.stdout       # the probe said why
