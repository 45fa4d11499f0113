"""What the TPU's compiler says about the serving kernels, asked without a
chip (``/opt/skills/guides/on-chip-measurement`` §2): each pallas kernel of
the llama3-8b path at its real widths, compiled for a DESCRIBED v5e device.
Interpret-mode tests cannot see what these see — tiling, VMEM budgets, and
(the case that was refused before PR 21) a Mosaic call inside a GSPMD program.
A compile that passes is not a chip run; ``chip_smoke.py`` is.
"""

import math
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the benchmark's cells as the kernel sees them: one table, the script's
from scripts.paged_kernel_bench import CELL_SHAPES
from scripts.program_copies import relaid_copies
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


def _paged_args(d, sharding, quant=False, layers=()):
    """(q, k_pool, v_pool[, k_scale, v_scale], table, lens) shapes;
    ``layers=(L,)`` stacks the pool as the engine holds it."""
    def s(shape, dt, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    pool = s((*layers, N_BLOCKS, BS, KH, d), pool_dt)
    scales = [s((*layers, N_BLOCKS, BS, KH), jnp.float32)] * 2 \
        if quant else []
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
    if name == "chunk":
        from tpu9.ops.chunk_attention import flash_chunk_prefill_attention
        return flash_chunk_prefill_attention, lambda sh: (
            dense(sh, 128)[0], dense(sh, 128)[1], dense(sh, 128)[1],
            jax.ShapeDtypeStruct((2,), jnp.int32, sharding=sh))
    if name == "paged":
        return paged_decode_attention, lambda sh: _paged_args(d, sh)
    return paged_decode_attention_quant, \
        lambda sh: _paged_args(d, sh, quant=True)


@pytest.mark.parametrize("name,head_dim", [
    ("flash", 128), ("ragged", 128), ("paged", 128), ("paged_int8", 128),
    # llama-1b: same head counts, head 64
    ("flash", 64), ("paged", 64), ("paged_int8", 64),
    # the chunk kernel over one layer's plane, two rows; heads that are not
    # 128 wide are loaded a head at a time (Mosaic's strided word load
    # wants 128 lanes)
    ("chunk", 128), ("chunk", 64), ("chunk", 256),
    # the mesh-sharded replica: the dispatcher's shard_map over a 4-chip
    # mesh with pool and q sharded on the head axis (refused before PR 21:
    # "Mosaic kernels cannot be automatically partitioned")
    ("paged_on_mesh", 128), ("paged_int8_on_mesh", 128),
    # the same over the whole stacked pool (ISSUE 25), at a layer that is
    # not the first: what a decode step of the engine dispatches
    ("paged_pool_on_mesh", 128), ("paged_int8_pool_on_mesh", 128),
])
def test_kernel_compiles_for_a_described_v5e(v5e, no_compile_cache,
                                             monkeypatch, name, head_dim):
    if name.endswith("_on_mesh"):
        # dispatch as on the chip: the sandbox's backend is the CPU
        monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
        mesh = Mesh(np.array(v5e).reshape(1, 1, 1, 4),
                    ("dp", "fsdp", "sp", "tp"))
        quant = "int8" in name
        layers, layer, pool, scales = ((3,), 2, attention_ops._POOL5,
                                       attention_ops._POOL4) \
            if "_pool_" in name else ((), 0, attention_ops._HEADS4,
                                      attention_ops._HEADS3)
        specs = [attention_ops._HEADS4, pool, pool,
                 *([scales] * 2 * quant), P(), P()]
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                     sharding=NamedSharding(mesh, spec))
                for a, spec in zip(
                    _paged_args(head_dim, None, quant, layers), specs)]
        if quant:
            def fn(q, k, v, ks, vs, table, lens):
                return paged_attention_dispatch(q, k, v, table, lens, ks,
                                                vs, mesh=mesh, layer=layer)
        else:
            def fn(q, k, v, table, lens):
                return paged_attention_dispatch(q, k, v, table, lens,
                                                mesh=mesh, layer=layer)
    else:
        from jax.sharding import SingleDeviceSharding
        fn, build = _kernel_case(name, head_dim)
        args = build(SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.endswith("_on_mesh"):
        # each chip holds a quarter of the k and v pools, and nothing
        # gathers them
        pools = 2 * args[1].size * args[1].dtype.itemsize
        assert compiled.memory_analysis().argument_size_in_bytes \
            < 0.3 * pools
        assert "all-gather" not in compiled.as_text()
        if "int8" not in name:
            # and why the wrapper exists: GSPMD alone refuses the kernel
            with pytest.raises(NotImplementedError, match="shard_map"):
                jax.jit(lambda q, k, v, t, n: paged_attention_dispatch(
                    q, k, v, t, n)).lower(*args).compile()


def _kernel_names(text: str) -> list:
    """The names, without their numbers, of the instructions of an optimised
    HLO text that are Mosaic kernels."""
    return [re.sub(r"[.\d]+$", "", ln.split(" = ", 1)[0].split()[-1]
                   .lstrip("%"))
            for ln in text.splitlines()
            if " custom-call(" in ln and "tpu_custom_call" in ln]


def _pool_copies(text: str, n_blocks: int) -> list:
    """The instructions of an optimised HLO text that copy an array as deep
    and as long as a pool of ``n_blocks`` blocks: a plane of it, whole."""
    return [ln.strip()[:160] for ln in text.splitlines() if re.search(
        rf"= \w+\[\d+,{n_blocks},[\d,]+\]\S* copy\(", ln)]


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_the_page_walk_compiles_at_a_cells_shapes(v5e, no_compile_cache,
                                                  monkeypatch, cell):
    """The dispatcher's call at a cell's widths, the whole stacked pool and
    a layer that is an operand: two KV heads a chip under ``shard_map`` with
    a 129-column table, sixteen heads without grouping and 9 columns, eight
    heads and 33, thirty-two without grouping and 24 (ISSUE 47: four blocks
    of eight heads an update). The kernel copies its pages itself (nothing
    of the pool is a temporary) and the compiler prints it under the step
    marker's name."""
    from benchmark.families import decoder
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    b, kh, group, columns, blocks, planes, chips = CELL_SHAPES[cell]
    mesh = Mesh(np.array(v5e[:chips]).reshape(1, 1, 1, chips),
                ("dp", "fsdp", "sp", "tp"))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    pool = s((planes, blocks, BS, kh, D), jnp.bfloat16, attention_ops._POOL5)
    args = [s((b, 1, kh * group, D), jnp.bfloat16, attention_ops._HEADS4),
            pool, pool, s((b, columns), jnp.int32, P()),
            s((b,), jnp.int32, P()), s((), jnp.int32, P())]
    compiled = jax.jit(
        lambda q, k, v, table, lens, layer: paged_attention_dispatch(
            q, k, v, table, lens, mesh=mesh, layer=layer)).lower(
                *args).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == [decoder.STEP_MARKER]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert "all-gather" not in text


@pytest.mark.parametrize("configuration", [
    "mixtral-8x7b-l4", "mistral-7b-v0.3-tp4", "ouro-2.6b",
    "evabyte-6.5b-l16"])
def test_a_decode_programs_only_kernels_are_the_step_markers(
        v5e, no_compile_cache, monkeypatch, configuration):
    """What the benchmark counts decode steps by (``STEP_MARKER``,
    ``marker_calls_per_step``): in a configuration's K = 1 decode program
    (two layers of it) the Mosaic kernels are an instruction named
    ``paged_decode_attention`` a layer — and, where the layers hold experts
    (ISSUE 50), one ``held_ffn`` a layer beside it — and no other
    instruction carries the marker's prefix: a helper kernel or a split into
    partial and combine calls would make the trace's step count wrong."""
    cfg, family, _, _, jobs = _decode_programs(v5e, monkeypatch,
                                               configuration, n_layers=2)
    (_, fn, args), = [job for job in jobs if job[0] == ("decode", 1)]
    text = fn.lower(*args).compile().as_text()
    experts = ["held_ffn"] * (cfg.n_layers if cfg.n_experts else 0)
    assert sorted(_kernel_names(text)) == sorted(
        [family.STEP_MARKER] * cfg.n_layers + experts)
    named = [ln for ln in text.splitlines() if re.match(
        rf"\s+(ROOT )?%?{family.STEP_MARKER}", ln)]
    assert len(named) == cfg.n_layers
    assert all("tpu_custom_call" in ln for ln in named)


def _decode_programs(v5e, monkeypatch, configuration, n_layers=None,
                     kinds=("decode",), cut=None):
    """``(cfg, family, n_chips, k_pool, jobs)``: a benchmark configuration's
    decode programs (or those of ``kinds``) at its engine's shapes (abstract
    arguments, no weights; as many described chips as its topology names),
    dispatching as on the chip; ``n_layers`` cuts the model's depth, and
    ``cut`` (config -> config) cuts what a count alone cannot."""
    from dataclasses import replace

    from benchmark import manifest, serve
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard.plan import parse_topology
    from tpu9.serving.shard.policy import MeshPolicy
    import tpu9.ops.grouped_ffn as grouped_ops
    import tpu9.ops.held_ffn as held_ops
    import tpu9.utils
    # every dispatcher: the three that hold the name, and the function the
    # KDA step and the latent attention look up at the call
    for module in (attention_ops, grouped_ops, held_ops, tpu9.utils):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    config = manifest.load_config(manifest.load(), configuration)
    family = manifest.family(config)
    cfg = family.program_config(family.model_sizes(config))
    if n_layers:
        cfg = replace(cfg, n_layers=n_layers)
    if cut:
        cfg = cut(cfg)
    ecfg = serve.engine_config(config["engine"])
    topology = parse_topology(config["engine"]["topology"])
    policy = MeshPolicy(topology, devices=v5e[:topology.n_chips])
    graphs = GraphFactory(cfg, ecfg, policy, chunk=ecfg.prefill_chunk)
    st = abstract_state(cfg, ecfg, policy)
    jobs = [job for job in graphs.lowering_jobs(
        abstract_params_for(cfg, False), st["kv_cache"], st["pool"],
        st["scratch"], st["mb"], [ecfg.prefill_chunk], (), st["rng"])
        if job[0][0] in kinds]
    return cfg, family, topology.n_chips, st["kv_cache"]["k"], jobs


# the scratch as the chunk kernel sees it (ISSUE 44): KV heads, query heads a
# KV head, max_seq_len, planes of the scratch, chips
SCRATCH_SHAPES = {"mistral-tp4-long": (8, 4, 16384, 32, 4),
                  "ouro-qa": (16, 1, 1024, 192, 1),
                  "mixtral": (8, 4, 4096, 4, 1)}
CHUNK_KERNEL = "chunk_prefill_attention"


@pytest.mark.parametrize("width", [128, 512])
@pytest.mark.parametrize("cell", list(SCRATCH_SHAPES))
def test_the_chunk_kernel_compiles_at_a_cells_shapes(v5e, no_compile_cache,
                                                     monkeypatch, cell,
                                                     width):
    """The dispatcher's call for a chunk and for an admission group at a
    cell's widths: the whole stacked scratch, a layer that is an operand,
    two KV heads a chip under ``shard_map`` on the four-chip mesh. The
    kernel reads the scratch where it lies: nothing of it is a temporary,
    nothing is gathered, and the compiler prints the kernel under its own
    name, which the decode step marker is no prefix of."""
    from benchmark.families import decoder
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    kh, group, s_max, planes, chips = SCRATCH_SHAPES[cell]
    mesh = Mesh(np.array(v5e[:chips]).reshape(1, 1, 1, chips),
                ("dp", "fsdp", "sp", "tp"))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    scratch = s((planes, 1, s_max, kh, D), jnp.bfloat16,
                attention_ops._POOL5)
    args = [s((1, width, kh * group, D), jnp.bfloat16,
              attention_ops._HEADS4), scratch, scratch,
            s((1, width), jnp.int32, P()), s((), jnp.int32, P())]
    compiled = jax.jit(
        lambda q, k, v, positions, layer:
        attention_ops.chunk_prefill_attention(
            q, k, v, positions, layer=layer, mesh=mesh)).lower(
                *args).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == [CHUNK_KERNEL]
    assert not CHUNK_KERNEL.startswith(decoder.STEP_MARKER)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert "all-gather" not in text


@pytest.mark.parametrize("configuration", [
    "mixtral-8x7b-l4", "mistral-7b-v0.3-tp4", "ouro-2.6b"])
def test_prefill_programs_attend_in_the_chunk_kernel(
        v5e, no_compile_cache, monkeypatch, configuration):
    """A configuration's ``chunk`` and admission-``group`` programs (two
    layers of it) at the benchmark's sizes, as the chip compiles them: the
    chunk kernel once a layer — under ``shard_map`` on four chips, once in
    the body of the looped family's pass loop — and no kernel with the
    decode step marker's name. What the XLA form kept in HBM is gone: no
    float32 ``[heads, queries, max_seq_len]`` (its scores; the hidden
    width of Mixtral is ``max_seq_len`` too, so the test is of the whole
    shape) and no scratch broadcast to the query heads (``_expand_gqa``)."""
    from benchmark import manifest
    cfg, family, n_chips, _, jobs = _decode_programs(
        v5e, monkeypatch, configuration, n_layers=2,
        kinds=("chunk", "chunkgroup"))
    engine = manifest.load_config(manifest.load(), configuration)["engine"]
    s_max, chunk = engine["max_seq_len"], engine["prefill_chunk"]
    widths = {("chunk", chunk): chunk,
              ("chunkgroup", engine["admit_group_chunks"]):
                  chunk * engine["admit_group_chunks"]}
    assert {key for key, _, _ in jobs} == set(widths)
    kh, group = cfg.n_kv_heads // n_chips, cfg.n_heads // cfg.n_kv_heads
    for key, fn, args in jobs:
        text = fn.lower(*args).compile().as_text()
        kernels = _kernel_names(text)
        assert kernels.count(CHUNK_KERNEL) == cfg.n_layers, key
        assert not [k for k in kernels
                    if k.startswith(family.STEP_MARKER)], key
        assert not re.search(rf"f32\[(1,)?{cfg.n_heads // n_chips},"
                             rf"{widths[key]},{s_max}\]", text), key
        assert group == 1 or not re.search(
            rf"\[[\d,]*{s_max},{kh},{group},{cfg.head_dim}\]", text), key


def _pool_shaped(text: str, pool: tuple) -> list:
    """Instructions of an optimised HLO text whose result is the pool or
    one layer's plane of it (with or without a leading 1), other than what
    carries the pool through the program (parameters, tuple plumbing) and
    the in-place scatter of the token write."""
    shapes = {",".join(map(str, dims))
              for dims in (pool, pool[1:], (1, *pool[1:]))}
    result = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                        r"([\w\-]+)\(")
    scatter_bodies, computation = set(), ""
    for line in text.splitlines():
        if not line.startswith(" "):
            computation = line.split(" ", 1)[0].lstrip("%")
        elif " ROOT " in f" {line.lstrip()}" and " scatter(" in line:
            scatter_bodies.add(computation)
    found = []
    for line in text.splitlines():
        m = result.match(line)
        if not m or m.group(1) not in shapes:
            continue
        op = m.group(2)
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        if op in ("parameter", "get-tuple-element", "scatter") or (
                op == "fusion" and calls
                and calls.group(1) in scatter_bodies):
            continue
        found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("configuration", ["mixtral-8x7b-l4",
                                           "mistral-7b-v0.3-tp4"])
def test_decode_programs_carry_the_pool_whole_on_a_described_v5e(
        v5e, no_compile_cache, monkeypatch, configuration):
    """The K = 1 and K = 8 decode programs at a benchmark configuration's
    engine shapes (abstract arguments, no weights; four chips for the
    tensor-parallel one): the compiler holds no second copy of the pool,
    no plane of it, and gathers none of it across chips."""
    cfg, _, n_chips, k_pool, programs = _decode_programs(
        v5e, monkeypatch, configuration)
    per_chip = (*k_pool.shape[:3], k_pool.shape[3] // n_chips,
                k_pool.shape[4])
    pool_bytes = 2 * int(np.prod(per_chip)) * k_pool.dtype.itemsize
    assert [key for key, _, _ in programs] == [("decode", 1), ("decode", 8)]
    for key, fn, args in programs:
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        # the paged kernel a layer, and the expert kernel where layers hold
        # experts (ISSUE 50)
        assert sorted(_kernel_names(text)) == sorted(
            ["paged_decode_attention"] * cfg.n_layers
            + ["held_ffn"] * (cfg.n_layers if cfg.n_experts else 0)), key
        assert compiled.memory_analysis().temp_size_in_bytes \
            < pool_bytes / 4, key
        assert _pool_shaped(text, per_chip) == [], key
        # the sampler gathers a few scalars per slot; nothing of the pool
        gathers = [ln for ln in text.splitlines() if " all-gather(" in ln]
        assert not [ln for ln in gathers if re.search(
            rf"\[[\d,]*{BS},{per_chip[3]},{per_chip[4]}\]", ln)], key


def test_the_sorted_moe_layer_compiles_at_mixtral_widths(
        v5e, no_compile_cache, monkeypatch):
    """An admission group's MoE layer (512 tokens, 8 experts of 4096 x
    14336, top-2) as the chip runs it: sort, gather, ONE grouped-FFN kernel
    over the tiles that hold rows, weighted sum. The kernel asks for more
    VMEM than the default scope (12 MB of weight blocks a step, twice),
    which only the TPU's compiler can refuse; and the layer keeps no
    ``[E, 512, 14336]`` temporary (117 MB each in the one-hot form)."""
    import tpu9.ops.grouped_ffn as grouped_ops
    from tpu9.models.moe import MoeConfig, moe_ffn_sorted
    monkeypatch.setattr(grouped_ops, "on_tpu", lambda: True)
    e, d, h, n = 8, 4096, 14336, 512
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = {"router": s((d, e), jnp.float32), "w_gate": s((e, d, h)),
              "w_up": s((e, d, h)), "w_down": s((e, h, d))}
    cfg = MoeConfig(dim=d, hidden_dim=h, n_experts=e, top_k=2,
                    capacity_factor=4.0, dtype=jnp.bfloat16)
    compiled = jax.jit(lambda p, x: moe_ffn_sorted(p, x, cfg)).lower(
        params, s((1, n, d))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_a_layer_patterns_kernels_compile_at_the_published_widths(
        v5e, no_compile_cache):
    """The KDA step of a layer pattern's decode at Ling-3.0-flash's widths
    and the cell's batch (ISSUE 48): 128 lanes x 32 heads of a float32
    ``[128, 128]`` state, plane 2 of 5, IN PLACE — the 1.34 GB array is
    aliased to the output and no copy of it is made."""
    from tpu9.ops import delta_rule
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    b, h, d, planes = 128, 32, 128, 5
    step = jax.jit(lambda st, q, k, v, la, be, live: delta_rule.step_pallas(
        st, 2, q, k, v, la, be, live), donate_argnums=(0,))
    compiled = step.lower(s((planes, b, h, d, d)), *[s((b, h, d))] * 4,
                          s((b, h)), s((b,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= planes * b * h * d * d * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


# a cell's decode step of latent attention: lanes, heads, the table's
# columns, the pool's planes and blocks (``ling-reason``, ``kimi-docs``)
LATENT_STEPS = {"ling-3.0-flash-l6-ep4": (128, 32, 33, 1, 4097),
                "kimi-k2.6-l6-ep32": (16, 64, 449, 6, 7184)}


@pytest.mark.parametrize("configuration", list(LATENT_STEPS))
def test_the_latent_decode_kernel_reads_both_planes_where_they_lie(
        v5e, no_compile_cache, configuration):
    """Latent attention's decode step at both cells' shapes (ISSUEs 48, 53):
    one 512-wide latent and one 64-wide rotated key a token for all heads,
    a lane's own pages copied where they lie — ONE ``tpu_custom_call``,
    nothing gathered in front of it and no float32 ``[lanes, heads, rows]``
    score beside it (Kimi's were 237 MB a call, and 1.9 GB a layer moved to
    have them). What the chip's compiler says of a 64-wide row, and only it
    says: a ``make_async_copy`` of one page's rotated keys ``[128, 64]`` out
    of a ``[L, N, 128, 64]`` plane is REFUSED — Mosaic holds that plane as
    ``memref<LxNx128x128xbf16, tiled<(8,128)(2,1)>>`` and "slice shape along
    dimension 3 must be aligned to tiling (128), but is 64"; a reshape of
    the plane to ``[L, N, 64, 128]`` in front of the kernel is ACCEPTED and
    copies the whole plane a call (1.42 GB of temporaries at Kimi's
    shapes). Held as ``[L, N, 64, 1, 128]`` — two tokens a row
    (``pack_rotated``) — the unit axis reshapes away for free and a page is
    one ``[64, 128]`` copy. A decode step's one row a lane goes into its
    half row in place: the plane is aliased, never copied."""
    from tpu9.models import kvstate
    from tpu9.ops import latent_attention
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    b, h, mb, planes, blocks = LATENT_STEPS[configuration]
    dc, dr, bs = 512, 64, 128
    rotated = s((planes, blocks, bs // 2, 1, 2 * dr))
    attend = jax.jit(lambda ql, qr, c, r, t, n:
                     latent_attention.paged_latent_attention_kernel(
                         ql, qr, c, r, t, n, planes - 1, 192 ** -0.5))
    compiled = attend.lower(
        s((b, h, dc)), s((b, h, dr)), s((planes, blocks, bs, 1, dc)),
        rotated, s((b, mb), jnp.int32), s((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == [latent_attention.LATENT_KERNEL]
    assert " gather(" not in text
    assert not re.search(rf"f32\[{b},{h},\d{{4,}}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20
    write = jax.jit(lambda pool, bi, oi, row: kvstate._packed_write(
        pool, planes - 1, bi, oi, row), donate_argnums=(0,))
    mem = write.lower(rotated, s((b,), jnp.int32), s((b,), jnp.int32),
                      s((b, dr))).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= planes * blocks * bs * dr * 2
    assert mem.temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_the_held_expert_layer_reads_its_stacks_as_they_are_stored(
        v5e, no_compile_cache, monkeypatch, form):
    """A decode step's expert layer of a chip's share at Ling-3.0-flash's
    widths (128 lanes, 128 held experts of 2560 x 768, 512 routed, top-8).
    As the chip runs it (ISSUE 49): ONE ``held_ffn`` kernel whose step holds
    an expert's three matrices whole, twice — 23.6 MB of VMEM, more than the
    default scope, which only the TPU's compiler can refuse. The XLA form:
    every held expert over every token as a BATCHED product — without the
    batch dimension the compiler took one product over all experts' columns
    and transposed both [128, 2560, 768] stacks a call, 1 GB of copies a
    layer, hoisted out of a K = 8 window as 5 GB of temporaries (found here,
    ISSUE 48). Neither form copies a stack."""
    import tpu9.ops.held_ffn as held_ops
    from tpu9.models.moe import MoeConfig, moe_ffn_held
    monkeypatch.setattr(held_ops, "on_tpu", lambda: form == "kernel")
    e, d, h, n = 128, 2560, 768, 128
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = {"router": s((d, 512), jnp.float32),
              "bias": s((512,), jnp.float32), "w_gate": s((e, d, h)),
              "w_up": s((e, d, h)), "w_down": s((e, h, d)),
              "shared": {"w_gate": s((d, h)), "w_up": s((d, h)),
                         "w_down": s((h, d))}}
    cfg = MoeConfig(dim=d, hidden_dim=h, n_experts=e, top_k=8, n_routed=512,
                    shared_dim=h, score="sigmoid", select_bias=True,
                    n_groups=8, top_groups=4, gate_scale=2.5,
                    dtype=jnp.bfloat16)
    compiled = jax.jit(lambda p, x, live: moe_ffn_held(p, x, cfg, live)).lower(
        params, s((n, 1, d)), s((n, 1), jnp.bool_)).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == (["held_ffn"] if form == "kernel" else [])
    assert not re.search(r"bf16\[128,(2560,768|768,2560)\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_a_plain_expert_decoders_step_keeps_no_product_over_every_expert(
        v5e, no_compile_cache, monkeypatch):
    """``mixtral-8x7b-l4``'s K = 1 decode program at its published widths and
    its engine's 32 lanes (ISSUE 50), two layers deep: the expert layer is
    the ``held_ffn`` kernel — its step three blocks of 4096 x 512 columns,
    12.6 MB, twice: VMEM that only the TPU's compiler can refuse — and no
    ``[8, 32, 14336]`` or ``[8, 32, 4096]`` product of the one-hot form over
    all eight experts is left; beside its tokens the program returns the
    chosen experts, ``[1, 32, 2, 2]``."""
    cfg, _, _, _, jobs = _decode_programs(v5e, monkeypatch,
                                          "mixtral-8x7b-l4", n_layers=2)
    (_, fn, args), = [job for job in jobs if job[0] == ("decode", 1)]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _kernel_names(text).count("held_ffn") == cfg.n_layers
    assert not re.search(r"bf16\[8,32,(14336|4096)\]", text)
    assert not re.search(r"bf16\[8,(4096,14336|14336,4096)\]\S* copy\(", text)
    picks = jax.ShapeDtypeStruct((1, 32, cfg.n_layers, 2), jnp.int32)
    assert [(o.shape, o.dtype) for o in jax.tree_util.tree_leaves(
        fn.eval_shape(*args))][-1] == (picks.shape, picks.dtype)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_a_layer_patterns_decode_window_holds_one_expert_kernel_a_layer(
        v5e, no_compile_cache, monkeypatch):
    """The cell's K = 8 decode program (``ling-3.0-flash-l6-ep4`` at its
    engine's shapes, ISSUE 49): five ``held_ffn`` calls, one an expert
    layer, one ``kda_state_step`` a KDA layer and ONE
    ``paged_latent_attention`` — the call the benchmark counts its steps
    by."""
    _, family, _, _, jobs = _decode_programs(v5e, monkeypatch,
                                             "ling-3.0-flash-l6-ep4")
    (_, fn, args), = [job for job in jobs if job[0] == ("decode", 8)]
    names = _kernel_names(fn.lower(*args).compile().as_text())
    assert sorted(names) == sorted(["held_ffn"] * 5
                                   + [family.KDA_STEP_KERNEL] * 5
                                   + [family.STEP_MARKER])


def test_a_layer_patterns_splice_writes_the_packed_plane_in_place(
        v5e, no_compile_cache, monkeypatch):
    """``ling-3.0-flash-l6-ep4``'s splice and group programs (ISSUE 53): a
    block of rotated keys is packed two tokens a row on its way into the
    pool's ONE plane, and the plane is not copied for it — as a single
    ``dynamic_update_slice`` the compiler tiled the whole plane to suit the
    update, three copies of 67 MB a group (``kvstate.splice_block``)."""
    *_, pool, jobs = _decode_programs(
        v5e, monkeypatch, "ling-3.0-flash-l6-ep4",
        kinds=("s", "chunkgroup"))
    assert [str(key) for key, _, _ in jobs] == ["splice", "('chunkgroup', 4)"]
    for key, fn, args in jobs:
        assert not _pool_copies(fn.lower(*args).compile().as_text(),
                                pool.shape[1]), key


@pytest.mark.parametrize("width", [512, 2048])
def test_the_latent_prefill_kernel_compiles_at_the_published_widths(
        v5e, no_compile_cache, width):
    """The blocked prefill of latent attention at Kimi-K2.6's widths and the
    cell's scratch (ISSUE 52): a chunk's or a group's queries, 64 heads,
    against a 57,344-row scratch six planes deep, read where it lies at a
    plane that is not the first. Eight heads' weights, queries and float32
    accumulators a grid step are more VMEM than the default scope, which
    only the TPU's compiler can refuse; nothing of the scratch's size, no
    expanded key or value, is kept outside the kernel."""
    from tpu9.ops import latent_attention
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    heads, rows, planes = 64, 57344, 6
    attend = jax.jit(lambda qn, qr, c, r, w, at:
                     latent_attention.blocked_prefill_attention_kernel(
                         qn, qr, c, r, w, at, 3, 0.1447))
    compiled = attend.lower(
        s((width, heads, 128)), s((width, heads, 64)),
        s((planes, 1, rows, 1, 512)), s((planes, 1, rows, 1, 64)),
        s((512, heads, 256)), s((), jnp.int32)).compile()
    assert _kernel_names(compiled.as_text()) == [
        latent_attention.PREFILL_KERNEL]
    # the queries and the weights laid out a head at a time, the output back
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_latent_attention_in_every_layer_holds_its_kernels(
        v5e, no_compile_cache, monkeypatch):
    """``kimi-k2.6-l6-ep32`` at its engine's shapes, two layers deep (one
    dense, one of experts): a decode step is one ``paged_latent_attention``
    a layer — the call the benchmark counts its steps by — and one
    ``held_ffn`` over the 12 held experts of 7,168 x 2,048; a chunk and a
    group are one ``latent_prefill_attention`` a layer and one grouped
    expert kernel; and the gather of a prefix's pages into the scratch
    keeps no copy of the pool (4.0 GB of temporaries taken along axis 1)."""
    from tpu9.ops import latent_attention
    cfg, family, _, pool, jobs = _decode_programs(
        v5e, monkeypatch, "kimi-k2.6-l6-ep32", n_layers=2,
        kinds=("decode", "chunk", "chunkgroup", "g", "s"))
    assert pool.shape == (2, 4865, 128, 1, 512)
    seen = {}
    for key, fn, args in jobs:
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        seen[key] = sorted(_kernel_names(text))
        if key == "gather":
            assert compiled.memory_analysis().temp_size_in_bytes \
                < 256 * 2 ** 20
        if key[0] == "decode":
            # ISSUE 53: no score over the table's width beside the kernel
            # (f32[16,64,57856] and four more operations a layer), and no
            # page of rotated keys gathered for one (bf16[7184,128,64])
            assert not re.search(r"f32\[16,64,\d{4,}\]", text), key
            assert not re.search(r"bf16\[\d+,128,64\]", text), key
        # and no program copies a plane of the pool: a scatter straight
        # into the ``[L, N, BS / 2, 1, 128]`` plane took it in another
        # tiling than the kernel's ``[L, N, BS / 2, 128]`` view of it, 0.5
        # GB copied there and back a layer of a decode step (PR 53's first
        # traced run); every write goes through ``kvstate._lanes_view``
        assert not _pool_copies(text, 4865), key
    assert family.STEP_MARKER == latent_attention.LATENT_KERNEL
    assert family.PREFILL_KERNEL == latent_attention.PREFILL_KERNEL
    for k in (1, 8):
        assert seen[("decode", k)] == sorted(
            ["held_ffn"] + [family.STEP_MARKER] * cfg.n_layers)
    for key in (("chunk", 512), ("chunkgroup", 4)):
        assert seen[key].count(family.PREFILL_KERNEL) == cfg.n_layers
        assert len(seen[key]) == cfg.n_layers + 1      # + the grouped FFN
    assert seen["gather"] == []


@pytest.mark.parametrize("b,h,p,n,g,planes", [
    (64, 64, 64, 128, 1, 36), (8, 8, 128, 64, 1, 3), (8, 64, 64, 128, 2, 3),
    (8, 16, 128, 256, 4, 2), (8, 8, 256, 128, 1, 2),
    (64, 128, 64, 128, 8, 5)],
    ids=["granite-4.0-h-micro", "state-64", "two-groups", "state-256",
         "two-tiles-wide", "nemotron-3-super"])
def test_the_state_space_step_compiles_at_the_published_widths(
        v5e, no_compile_cache, b, h, p, n, g, planes):
    """The Mamba-2 step of a listed pattern's decode at Granite 4.0-H
    Micro's widths and the cell's lanes (ISSUE 55): 64 lanes x 64 heads of a
    float32 ``[64, 128]`` state, plane 1 of 36, IN PLACE — the 4.83 GB array
    stays where it lies (``pl.ANY``), aliased to the output, and no copy of
    it is made — the live lanes a prefetched list that ONE invocation walks
    behind its own copies (ISSUE 56); and at every other kind of shape
    ``step_kernel_declined`` lets through: a state narrower and wider than
    a register's 128 lanes, several groups, a row two registers wide; and at
    Nemotron 3 Super's (ISSUE 59): 128 heads of 64 in 8 groups, a lane's
    block 4.19 MB, eight of them 33.5 MB of VMEM a group of lanes."""
    from tpu9.ops import ssd
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert not ssd.step_kernel_declined(h, p, n, g).replace(
        "no TPU backend", "")
    stored = ssd.state_shape(h, p, n, g)
    if planes == 36:
        assert stored == (32, 128, 128)     # two heads side by side a row
    if h == 128:
        assert stored == (64, 128, 128)     # 16 heads a group: 8 rows of 2
    step = jax.jit(lambda st, x, dt, a, bm, cm, live: ssd.step_pallas(
        st, 1, x, dt, a, bm, cm, live), donate_argnums=(0,))
    compiled = step.lower(s((planes, b) + stored), s((b, h, p)), s((b, h)),
                          s((h,)), s((b, g, n)), s((b, g, n)),
                          s((b,), jnp.bool_)).compile()
    assert _kernel_names(compiled.as_text()) == [ssd.STEP_KERNEL]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= planes * b * h * p * n * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_a_listed_pattern_holds_its_kernels_at_the_published_widths(
        v5e, no_compile_cache, monkeypatch):
    """``granite-4.0-h-micro`` at its engine's shapes, three layers deep (a
    state-space layer each side of an attention layer): a decode step is
    one ``ssm_state_step`` a state-space layer, in place over the lanes'
    0.27 GB of state here (4.83 GB at 36 planes), and one
    ``paged_decode_attention`` — the call the benchmark counts its steps by
    — over rows of two 64-wide heads; a
    chunk and a group attend in the chunk kernel; no program copies the
    state."""
    from dataclasses import replace

    from tpu9.models import kvstate
    from tpu9.ops import ssd
    cfg, family, _, pool, jobs = _decode_programs(
        v5e, monkeypatch, "granite-4.0-h-micro",
        kinds=("decode", "chunk", "chunkgroup", "l"),
        cut=lambda c: replace(c, n_layers=3,
                              layer_pattern=("ssm", "full", "ssm")))
    # two KV heads of 64 a row of 128 lanes (``kvstate.heads_per_row``)
    assert pool.shape == (1, 801, 128, 4, 128)
    state = kvstate.lane_shapes(cfg, 64)["ssm_state"][0]
    assert state == (2, 64, 32, 128, 128)
    shaped = "f32[" + ",".join(str(n) for n in state) + "]"
    seen = {}
    for key, fn, args in jobs:
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        seen[key] = sorted(_kernel_names(text))
        if key[0] == "decode":
            # the whole state is an operand and a result, and never a copy
            assert not re.search(r"= " + re.escape(shaped) + r"[^=]* copy\(",
                                 text), key
            # and no program copies a plane of the pool (a pool of 64-wide
            # heads was laid out anew at every program's door and every
            # write: PR 55's first traced run, 37 % of a step)
            assert not _pool_copies(text, 801), key
            # the states array stays where it lies (``pl.ANY``) and is
            # still aliased to the kernels' output: the program's arguments
            # are its results, and its temporaries hold no buffer of the
            # array's size (268 MB here)
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes >= math.prod(state) * 4, key
            assert mem.temp_size_in_bytes < math.prod(state) * 4, key
    assert family.STEP_MARKER == "paged_decode_attention"
    assert family.SSM_STEP_KERNEL == ssd.STEP_KERNEL
    for k in (1, 8):
        assert seen[("decode", k)] == sorted(
            [family.STEP_MARKER] + [ssd.STEP_KERNEL] * 2)
    for key in (("chunk", 512), ("chunkgroup", 2)):
        assert seen[key] == [CHUNK_KERNEL]
    assert seen["lanesplice"] == []


def test_a_list_of_half_layers_holds_its_kernels_at_the_published_widths(
        v5e, no_compile_cache, monkeypatch):
    """``nemotron-3-super-l11-ep4`` at its engine's shapes, one layer of each
    kind deep (``M*E``, ISSUE 59): a decode step is one ``ssm_state_step``
    over 128 heads in 8 groups, in place, one ``paged_decode_attention`` —
    the call the benchmark counts its steps by — over a pool ONE plane deep
    of two 128-wide heads, and one UNGATED ``held_ffn`` whose step holds an
    expert's two matrices of 1,024 x 2,688 whole, twice (22 MB of VMEM); a
    chunk and a group attend in the chunk kernel and sort their rows into
    ``grouped_ffn`` at the whole hidden width; beside its tokens a window
    returns the picks of its one expert layer; no program copies the state
    or an expert stack."""
    from dataclasses import replace

    from tpu9.models import kvstate
    from tpu9.ops import ssd
    cfg, family, _, pool, jobs = _decode_programs(
        v5e, monkeypatch, "nemotron-3-super-l11-ep4",
        kinds=("decode", "chunk", "chunkgroup", "l"),
        cut=lambda c: replace(c, n_layers=3,
                              layer_pattern=("ssm", "full", "none"),
                              ffn_pattern=("none", "none", "experts")))
    assert pool.shape == (1, 2049, 128, 2, 128)
    state = kvstate.lane_shapes(cfg, 64)["ssm_state"][0]
    assert state == (1, 64, 64, 128, 128)
    shaped = "f32[" + ",".join(str(n) for n in state) + "]"
    seen = {}
    for key, fn, args in jobs:
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        seen[key] = sorted(_kernel_names(text))
        # an expert stack is read where it lies
        assert not re.search(
            r"bf16\[128,(1024,2688|2688,1024)\]\S* copy\(", text), key
        if key[0] == "decode":
            assert not re.search(r"= " + re.escape(shaped) + r"[^=]* copy\(",
                                 text), key
            assert not _pool_copies(text, 2049), key
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes >= math.prod(state) * 4, key
            assert mem.temp_size_in_bytes < 256 * 2 ** 20, key
            picks = jax.tree_util.tree_leaves(fn.eval_shape(*args))[-1]
            assert picks.shape == (key[1], 64, 1, 22)
    assert family.EXPERT_STEP_KERNEL == "held_ffn"
    for k in (1, 8):
        assert seen[("decode", k)] == sorted(
            [family.STEP_MARKER, ssd.STEP_KERNEL, family.EXPERT_STEP_KERNEL])
    for key in (("chunk", 512), ("chunkgroup", 4)):
        assert seen[key] == sorted([CHUNK_KERNEL, "grouped_ffn"])
    assert seen["lanesplice"] == []


def test_a_list_around_short_convolutions_holds_its_kernels_at_the_published_widths(
        v5e, no_compile_cache, monkeypatch):
    """``lfm2-8b-a1b-l14`` at its engine's shapes, a layer of each kind deep
    (a conv layer closed by the dense SwiGLU, then attention and a conv layer
    closed by experts, ISSUE 62): a decode step is one
    ``paged_decode_attention`` — the call the benchmark counts its steps by —
    over a pool of 64-wide heads two a 128-lane row, one gated ``held_ffn``
    an expert layer, and NO kernel for the mixers; a chunk and a group attend
    in the chunk kernel and sort their rows into ``grouped_ffn``; the splice
    carries the pages' tails in place, the restore reads one page's; beside
    its tokens a window returns the picks of its expert layers; no program
    copies the pool, the tails' plane or an expert stack."""
    from dataclasses import replace

    from tpu9.models import kvstate
    cfg, family, _, pool, jobs = _decode_programs(
        v5e, monkeypatch, "lfm2-8b-a1b-l14",
        kinds=("decode", "chunk", "chunkgroup", "s", "t", "l"),
        cut=lambda c: replace(c, n_layers=3, moe_dense_layers=1,
                              layer_pattern=("conv", "full", "conv")))
    assert pool.shape == (1, 4481, 128, 4, 128)
    tails = kvstate.block_tail_shapes(cfg, 4481)[kvstate.BLOCK_TAIL][0]
    assert tails == (2, 4481, 2, 2048)
    seen = {}
    for key, fn, args in jobs:
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        seen[key] = sorted(_kernel_names(text))
        assert not re.search(
            r"bf16\[32,(2048,1792|1792,2048)\]\S* copy\(", text), key
        assert not re.search(r"= bf16\[2,4481,2,2048\]\S* copy\(", text), key
        if key[0] == "decode":
            assert not _pool_copies(text, 4481), key
            assert compiled.memory_analysis().temp_size_in_bytes \
                < 256 * 2 ** 20, key
            picks = jax.tree_util.tree_leaves(fn.eval_shape(*args))[-1]
            assert picks.shape == (key[1], 32, 2, 4)
    for k in (1, 8):
        assert seen[("decode", k)] == sorted(
            [family.STEP_MARKER] + [family.EXPERT_STEP_KERNEL] * 2)
    for key in (("chunk", 512), ("chunkgroup", 4)):
        assert seen[key] == sorted([CHUNK_KERNEL] + ["grouped_ffn"] * 2)
    assert seen["splice"] == seen["tailrestore"] == seen["lanesplice"] == []


def test_a_looped_decode_program_carries_the_pool_through_its_pass_loop(
        v5e, no_compile_cache, monkeypatch):
    """The looped configuration at its published widths, two layers deep:
    the passes are ONE device loop whose body holds the layers' kernels
    once (the plane ``u * n_layers + l`` is an operand), and the compiler
    keeps the ``kv_layers``-deep pool in place through it — no copy, no
    plane of it."""
    cfg, _, _, k_pool, jobs = _decode_programs(v5e, monkeypatch, "ouro-2.6b",
                                               n_layers=2)
    assert cfg.loop_steps == 4 and cfg.kv_layers == 8
    pool = k_pool.shape
    assert pool[0] == 8
    (_, fn, args), = [job for job in jobs if job[0] == ("decode", 1)]
    text = fn.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == cfg.n_layers
    assert " while(" in text
    assert _pool_shaped(text, pool) == []


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("configuration", [
    "evabyte-6.5b-l16", "ouro-2.6b", "mistral-7b-v0.3-tp4"])
def test_a_decode_program_reads_its_weights_as_they_are_stored(
        v5e, no_compile_cache, monkeypatch, configuration, k):
    """A decode program two layers deep at its engine's shapes (ISSUE 63;
    four described chips for the tensor-parallel one, whose shards are the
    parameters there): no ``copy`` or ``copy-start`` writes a weight — or a
    ``ConcatBitcast`` of a weight's slices, or a bitcast that reads it as
    its transpose — out again in another layout than the one it is stored
    in. ``wq`` / ``wk`` / ``wv`` were: their products, split into heads,
    folded the split in at a step's few rows and wanted the contracted
    dimension minor, so every CALL transposed them whole before its first
    step (1.6 GB a call on ``evabyte-6.5b-l16``, 1.2 GB on ``ouro-2.6b``:
    into temporaries in HBM, or at K = 1 into VMEM). The rest of a
    program's arguments (a table, the lengths) are a call's own and small:
    not held here."""
    _, _, _, _, jobs = _decode_programs(v5e, monkeypatch, configuration,
                                        n_layers=2)
    (_, fn, args), = [job for job in jobs if job[0] == ("decode", k)]
    text = fn.lower(*args).compile().as_text()
    assert [(c["parameter"], c["shape"], c["layout"], c["where"])
            for c in relaid_copies(text)
            if c["parameter"].startswith("params")] == []


RELAID = '''HloModule jit_decode
%fused_copy (param_0.1: bf16[64,256]) -> bf16[64,256] {
  %param_0.1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[64,256]{0,1:T(8,128)(2,1)} copy(%param_0.1)
}
%body (state: (s32[], bf16[256,256], bf16[256,256])) -> (s32[], bf16[256,256], bf16[256,256]) {
  %state = (s32[]{:T(128)}, bf16[256,256]{1,0:T(8,128)(2,1)}, bf16[256,256]{0,1:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[256,256]{1,0:T(8,128)(2,1)} get-tuple-element(%state), index=1
  %gte.2 = bf16[256,256]{0,1:T(8,128)(2,1)} get-tuple-element(%state), index=2
  %copy-start.1 = (bf16[256,256]{1,0:T(8,128)(2,1)S(1)}, bf16[256,256]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%gte.1)
  %copy-start.2 = (bf16[256,256]{0,1:T(8,128)(2,1)S(1)}, bf16[256,256]{0,1:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%gte.2)
  %copy.5 = bf16[256,256]{0,1:T(8,128)(2,1)} copy(%gte.1)
}
ENTRY %main.7 (wq: bf16[256,256], wk: bf16[256,256], wo: bf16[64,256], x: bf16[16,256]) -> bf16[16,256] {
  %params__wq__.1 = bf16[256,256]{1,0:T(8,128)(2,1)} parameter(0)
  %params__wk__.1 = bf16[256,256]{1,0:T(8,128)(2,1)} parameter(1)
  %params__wo__.1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(2)
  %x.1 = bf16[16,256]{1,0:T(8,128)(2,1)} parameter(3)
  %slice-start = ((bf16[256,256]{1,0:T(8,128)(2,1)}), bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%params__wk__.1), slice={[0:128], [0:256]}
  %slice-done = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start)
  %slice-start.1 = ((bf16[256,256]{1,0:T(8,128)(2,1)}), bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%params__wk__.1), slice={[128:256], [0:256]}
  %slice-done.1 = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)
  %custom-call.3 = bf16[256,256]{1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done, %slice-done.1), custom_call_target="ConcatBitcast"
  %copy.2 = bf16[256,256]{0,1:T(8,128)(2,1)} copy(%custom-call.3)
  %copy-start.7 = (bf16[256,256]{1,0:T(8,128)(2,1)S(1)}, bf16[256,256]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%params__wq__.1)
  %copy-done.7 = bf16[256,256]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.7)
  %copy.1 = bf16[256,256]{0,1:T(8,128)(2,1)} copy(%copy-done.7)
  %copy.3 = bf16[16,256]{0,1:T(8,128)(2,1)} copy(%dot.4)
  %bitcast.4 = bf16[256,256]{0,1:T(8,128)(2,1)} bitcast(%params__wq__.1)
  %copy.4 = bf16[256,256]{1,0:T(8,128)(2,1)S(1)} copy(%bitcast.4)
  %fusion.8 = bf16[64,256]{0,1:T(8,128)(2,1)} fusion(%params__wo__.1), kind=kLoop, calls=%fused_copy
  %tuple.6 = (s32[]{:T(128)}, bf16[256,256]{1,0:T(8,128)(2,1)}, bf16[256,256]{0,1:T(8,128)(2,1)}) tuple(%zero, %params__wq__.1, %copy.1)
  %while.5 = (s32[]{:T(128)}, bf16[256,256]{1,0:T(8,128)(2,1)}, bf16[256,256]{0,1:T(8,128)(2,1)}) while(%tuple.6), condition=%cond, body=%body
}
'''


@pytest.mark.parametrize("copy,parameter,where,order", [
    # a prefetched weight, transposed at the program's door
    ("copy.1", "params__wq__.1", "entry", "0,1"),
    # a weight's slices, put together and transposed
    ("copy.2", "params__wk__.1", "entry", "0,1"),
    # the same weight through the loop's state, transposed every step
    ("copy.5", "params__wq__.1", "loop", "0,1"),
    # a fusion that is a transposing copy of its parameter
    ("copy.9", "params__wo__.1", "entry", "0,1"),
    # a square weight read as its transpose, then copied into the stored
    # order of dimensions: the K = 1 programs' form
    ("copy.4", "params__wq__.1", "entry", "1,0"),
], ids=["prefetched", "slices", "in_loop", "fusion", "bitcast"])
def test_relaid_copies_finds_a_weight_written_out_in_another_layout(
        copy, parameter, where, order):
    """The reader above (``scripts/program_copies.py``), on hand-made text:
    what it finds — and that a prefetch in the weight's own layout
    (``copy-start.7``, ``copy-start.1``), a copy of what the program
    computed (``copy.3``) and the prefetch of an already transposed
    temporary (``copy-start.2``) are none of it."""
    found = {c["copy"]: c for c in relaid_copies(RELAID)}
    assert sorted(found) == ["copy.1", "copy.2", "copy.4", "copy.5",
                             "copy.9"]
    assert (found[copy]["parameter"], found[copy]["where"],
            found[copy]["layout"]) == (parameter, where,
                                       order + ":T(8,128)(2,1)")
    assert found[copy]["parameter_layout"] == "1,0:T(8,128)(2,1)"


def test_pool_shaped_finds_what_the_old_program_did():
    """The reader above, on hand-made text: a plane cut out, planes stacked
    back and the compiler's own pool-shaped copy are found; parameters, the
    loop's plumbing and the in-place scatter are not."""
    text = '''HloModule jit_decode
%fused_scatter (p0: bf16[4,9,128,8,128], p1: s32[2], p2: bf16[2,8,128]) -> bf16[4,9,128,8,128] {
  %p0 = bf16[4,9,128,8,128]{4,3,2,1,0} parameter(0)
  ROOT %scatter.1 = bf16[4,9,128,8,128]{4,3,2,1,0} scatter(%p0, %p1, %p2), to_apply=%region
}
%fused_slice (p0.1: bf16[4,9,128,8,128]) -> bf16[9,128,8,128] {
  %p0.1 = bf16[4,9,128,8,128]{4,3,2,1,0} parameter(0)
  ROOT %bitcast.2 = bf16[9,128,8,128]{3,2,1,0} bitcast(%slice.1)
}
ENTRY %main (k: bf16[4,9,128,8,128]) -> bf16[4,9,128,8,128] {
  %k = bf16[4,9,128,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %gte = bf16[4,9,128,8,128]{4,3,2,1,0} get-tuple-element(%while), index=2
  %fusion.4 = bf16[4,9,128,8,128]{4,3,2,1,0} fusion(%k, %i, %u), kind=kCustom, calls=%fused_scatter
  %slice_bitcast_fusion = bf16[9,128,8,128]{3,2,1,0} fusion(%k), kind=kLoop, calls=%fused_slice
  %pad_maximum_fusion = bf16[4,9,128,8,128]{4,3,2,1,0} fusion(%a, %b), kind=kLoop, calls=%fused_pad
  %copy-done.3 = bf16[1,9,128,8,128]{4,3,2,1,0} copy-done(%copy-start.3)
  %fusion.9 = bf16[2,8,4,128]{3,2,1,0} fusion(%q), kind=kLoop, calls=%fused_q
}
'''
    found = _pool_shaped(text, (4, 9, 128, 8, 128))
    assert [ln.split(" ", 1)[0] for ln in found] == [
        "ROOT", "%slice_bitcast_fusion", "%pad_maximum_fusion",
        "%copy-done.3"]


@pytest.mark.multichip
@pytest.mark.parametrize("layers,layer", [((), 0), ((3,), 2)],
                         ids=["plane", "pool_layer_2"])
def test_paged_kernel_under_shard_map_matches_the_oracle(layers, layer):
    """The same shard_map wrapping, RUN: four virtual CPU devices, the
    kernel interpreted, against the XLA oracle on unsharded inputs — over
    one layer's plane, and over the stacked pool at a layer that is not
    the first."""
    import functools
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                ("dp", "fsdp", "sp", "tp"))
    b, qh, kh, d, bs, mb = 2, 8, 4, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, qh, d), jnp.float32)
    kp = jax.random.normal(ks[1], (*layers, b * mb + 1, bs, kh, d),
                           jnp.float32)
    vp = jax.random.normal(ks[2], (*layers, b * mb + 1, bs, kh, d),
                           jnp.float32)
    table = (jnp.arange(b * mb, dtype=jnp.int32) + 1).reshape(b, mb)
    lens = jnp.asarray([bs * mb, bs + 3], jnp.int32)
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    pool_spec = attention_ops._POOL5 if layers else attention_ops._HEADS4
    pool_heads = NamedSharding(mesh, pool_spec)
    sharded = attention_ops._per_chip_heads(
        functools.partial(paged_decode_attention, interpret=True), mesh,
        (attention_ops._HEADS4, pool_spec, pool_spec, P(), P(), P()))
    got = jax.jit(sharded)(jax.device_put(q, heads),
                           jax.device_put(kp, pool_heads),
                           jax.device_put(vp, pool_heads), table, lens,
                           jnp.int32(layer))
    want = xla_paged_decode_attention(q, kp, vp, table, lens, layer=layer)
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
