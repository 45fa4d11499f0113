"""graphcheck (ISSUE 11): Pass B rule fixtures, Pass A negative fixtures
(each seeded violation must produce exactly its rule's finding), the
recompile sentinel, the json schema round-trip, the new boundary edges,
and the tier-1 gate itself (this test IS the wiring, next to
test_lint.py)."""

import ast
import json
import os
import sys
import textwrap
from dataclasses import replace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import graph_gate  # noqa: E402

from tpu9.analysis import boundaries as bnd  # noqa: E402
from tpu9.analysis.findings import (JSON_FIELDS, finding_from_json,  # noqa: E402
                                    finding_json)
from tpu9.analysis.graphcheck import astrules  # noqa: E402
from tpu9.analysis.graphcheck import passes  # noqa: E402
from tpu9.analysis.graphcheck.matrix import MATRIX, Cell, find_cells  # noqa: E402


def check(src: str, path: str = "tpu9/serving/spec.py"):
    tree = ast.parse(textwrap.dedent(src))
    return astrules.check_graph_file(path, tree)


def rule_ids(src: str, path: str = "tpu9/serving/spec.py"):
    return sorted({f.rule for f in check(src, path)})


# ---------------------------------------------------------------------------
# Pass B — SHD001: jit ownership
# ---------------------------------------------------------------------------

class TestSHD001:
    SRC = """
    import jax
    def build(fn):
        return jax.jit(fn)
    """

    def test_jit_outside_factory_flagged(self):
        fs = [f for f in check(self.SRC) if f.rule == "SHD001"]
        assert len(fs) == 1
        assert "GraphFactory" in fs[0].message

    def test_jit_in_owner_files_not_flagged(self):
        assert check(self.SRC, path="tpu9/serving/graphs.py") == []
        assert check(self.SRC, path="tpu9/serving/shard/policy.py") == []

    def test_jit_with_out_shardings_not_flagged(self):
        src = """
        import jax
        def build(fn, sh):
            return jax.jit(fn, out_shardings=sh)
        """
        assert "SHD001" not in rule_ids(src)

    def test_outside_mesh_scope_not_flagged(self):
        assert check(self.SRC, path="tpu9/train/loop.py") == []


# ---------------------------------------------------------------------------
# Pass B — SHD002: donated-buffer reuse
# ---------------------------------------------------------------------------

class TestSHD002:
    def test_reuse_after_donation_flagged(self):
        src = """
        import jax
        def step(params, kv, tok):
            f = jax.jit(decode, donate_argnums=(1,))
            out = f(params, kv, tok)
            return kv.sum()          # kv is DEAD: donated to f
        """
        fs = [f for f in check(src) if f.rule == "SHD002"]
        assert len(fs) == 1
        assert "kv" in fs[0].message and "donated" in fs[0].message.lower()

    def test_roundtrip_rebind_not_flagged(self):
        src = """
        import jax
        def step(params, kv, tok):
            f = jax.jit(decode, donate_argnums=(1,))
            tok, kv = f(params, kv, tok)
            return kv.sum()          # rebound from the result: fine
        """
        assert "SHD002" not in rule_ids(src)

    def test_same_line_pre_call_store_does_not_mask(self):
        # `kv = make(); out = f(..., kv, ...)` on ONE line: the pre-call
        # store shares the call's line but is NOT the round-trip rebind —
        # the later read of the donated buffer must still be flagged
        src = """
        import jax
        def step(params, tok):
            f = jax.jit(decode, donate_argnums=(1,))
            kv = make(); out = f(params, kv, tok)
            return kv.sum()
        """
        assert "SHD002" in rule_ids(src)

    def test_non_donated_arg_reuse_not_flagged(self):
        src = """
        import jax
        def step(params, kv, tok):
            f = jax.jit(decode, donate_argnums=(1,))
            out = f(params, kv, tok)
            return params, tok       # only arg 1 was donated
        """
        assert "SHD002" not in rule_ids(src)

    def test_attribute_buffers_tracked(self):
        src = """
        import jax
        class E:
            def step(self):
                self.f = jax.jit(decode, donate_argnums=(0,))
                out = self.f(self.kv)
                return self.kv       # donated attribute read back
        """
        fs = [f for f in check(src) if f.rule == "SHD002"]
        assert len(fs) == 1 and "self.kv" in fs[0].message


# ---------------------------------------------------------------------------
# Pass B — DTY001: raw int8 KV symbols
# ---------------------------------------------------------------------------

class TestDTY001:
    def test_undeclared_importer_flagged(self):
        src = "from tpu9.ops.quant import quantize_kv\n"
        fs = [f for f in check(src, path="tpu9/router/affinity.py")
              if f.rule == "DTY001"]
        assert len(fs) == 1
        assert "carrier" in fs[0].message or "carriers" in fs[0].message

    def test_relative_import_resolved(self):
        src = "from ..ops.quant import dequantize_kv\n"
        fs = check(src, path="tpu9/worker/weightstream.py")
        assert [f.rule for f in fs] == ["DTY001"]

    def test_declared_carriers_not_flagged(self):
        src = "from ..ops.quant import quantize_kv\n"
        assert check(src, path="tpu9/models/kvstate.py") == []
        assert check(src, path="tpu9/ops/paged_attention.py") == []
        # since ISSUE 51 the cache's format has one owner: the model step
        # and the graph factory see int8 rows through it
        for path in ("tpu9/serving/graphs.py", "tpu9/models/transformer.py"):
            assert [f.rule for f in check(src, path=path)] == ["DTY001"]

    def test_non_raw_symbols_not_flagged(self):
        src = "from tpu9.ops.quant import validate_quant_mode\n"
        assert check(src, path="tpu9/router/affinity.py") == []


# ---------------------------------------------------------------------------
# Pass A — fixtures (multichip tier: the forced 8-device CPU mesh)
# ---------------------------------------------------------------------------

TINY = Cell("llama-tiny", "2x1", n_layers=2, max_batch=2, max_seq_len=128,
            kv_block_size=32, chunk=32, decode_steps=(1, 2), spec_len=2,
            admit_group_chunks=2, kv_pool_blocks=4)


def _tiny_objects(topology="2x1", cell=TINY, policy=None):
    cell = replace(cell, topology=topology)
    built = passes.build_cell(cell)
    cfg, ecfg, pol, factory, params, state, buckets, spec_lens = built
    if policy is not None:
        # seed a broken policy into the factory AND the abstract state
        from tpu9.serving.graphs import GraphFactory, abstract_state
        pol = policy(pol)
        state = abstract_state(cfg, ecfg, pol, kv_quant=bool(cell.kv_quant))
        factory = GraphFactory(cfg, ecfg, pol, chunk=cell.chunk,
                               kv_quant=bool(cell.kv_quant))
    jobs = list(factory.lowering_jobs(
        params, state["kv_cache"], state["pool"], state["scratch"],
        state["mb"], buckets, spec_lens, state["rng"]))
    return cell, cfg, pol, factory, jobs, buckets, spec_lens


@pytest.mark.multichip
def test_clean_tiny_cell_no_findings():
    cell, cfg, pol, factory, jobs, buckets, spec_lens = _tiny_objects()
    for key, fn, args in jobs:
        assert passes.check_job(cell, cfg, pol, key, fn, args) == [], key
    assert passes.signature_findings(
        cell.name, {k for k, _, _ in jobs},
        factory.reachable_keys(buckets, spec_lens)) == []


@pytest.mark.multichip
@pytest.mark.parametrize("graph", [("decode", 1), ("chunkgroup", 2)],
                         ids=["decode", "chunkgroup"])
def test_missing_constrain_kv_is_gra002(graph):
    """Seeded violation: a policy whose constrain_kv is the identity —
    the pool outputs leave the graph unpinned (the admission group's pool
    and scratch too: it is a flat program, pinned at its own top level)."""
    def strip_constraint(pol):
        class NoConstraint(pol.__class__):
            def __init__(self):
                self.__dict__.update(pol.__dict__)

            def constrain_kv(self, tree):
                return tree
        return NoConstraint()

    cell, cfg, pol, factory, jobs, *_ = _tiny_objects(
        policy=strip_constraint)
    key, fn, args = next(j for j in jobs if j[0] == graph)
    fs = passes.check_job(cell, cfg, pol, key, fn, args,
                          compile_jobs=False)
    assert fs and {f.rule for f in fs} == {"GRA002"}
    assert any("constrain_kv" in f.message for f in fs)


@pytest.mark.multichip
def test_constraint_on_single_device_is_gra002():
    """The inverse: a 1x1 policy that inserts constraints breaks the
    bit-identical single-device graph contract."""
    def leaky(pol):
        import jax
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)
        import numpy as np

        class Leaky(pol.__class__):
            def __init__(self):
                self.__dict__.update(pol.__dict__)
                self._m = Mesh(np.array(jax.devices()[:1]), ("tp",))

            def constrain_kv(self, tree):
                return {n: jax.lax.with_sharding_constraint(
                            a, NamedSharding(self._m, P()))
                        for n, a in tree.items()}
        return Leaky()

    cell, cfg, pol, factory, jobs, *_ = _tiny_objects(
        topology="1x1", policy=leaky)
    key, fn, args = next(j for j in jobs if j[0] == ("decode", 1))
    fs = passes.check_job(cell, cfg, pol, key, fn, args,
                          compile_jobs=False)
    assert [f.rule for f in fs] == ["GRA002"]
    assert "SINGLE-DEVICE" in fs[0].message


@pytest.mark.multichip
def test_replicated_weights_under_tp2_is_gra001():
    """Seeded violation: a policy that silently replicates every weight
    (the layout rule 'resolved' nothing) under tp=2."""
    def replicating(pol):
        import jax
        from jax.sharding import PartitionSpec as P

        class Replicating(pol.__class__):
            def __init__(self):
                self.__dict__.update(pol.__dict__)

            def param_specs(self, tree):
                declared, _resolved = super().param_specs(tree)
                repl = jax.tree_util.tree_map(
                    lambda s: P(), declared,
                    is_leaf=lambda x: isinstance(x, P))
                return declared, repl
        return Replicating()

    cell, cfg, pol, factory, jobs, *_ = _tiny_objects(policy=replicating)
    key, fn, args = next(j for j in jobs if j[0] == ("decode", 1))
    fs = passes.check_job(cell, cfg, pol, key, fn, args)
    rules = {f.rule for f in fs}
    assert "GRA001" in rules
    assert any("REPLICATED" in f.message or "replicated" in f.message
               for f in fs if f.rule == "GRA001")


@pytest.mark.multichip
def test_dropped_donation_alias_is_gra003():
    """Seeded violation: a graph that donates a buffer no output can
    alias (shape changes) — XLA silently drops the donation."""
    import jax
    import jax.numpy as jnp

    cell, cfg, pol, *_ = _tiny_objects(topology="1x1")
    fn = jax.jit(
        lambda pool: {"k": pool["k"][..., :1] * 2,     # shape changed:
                      "v": pool["v"][..., :1] * 2},    # nothing to alias
        donate_argnums=(0,))
    dt = cfg.dtype
    pool = {"k": jax.ShapeDtypeStruct((4, 8), dt),
            "v": jax.ShapeDtypeStruct((4, 8), dt)}
    fs = passes.check_job(cell, cfg, pol, "splice", fn, (pool, "x", "y",
                                                         0, 0)[:1])
    assert fs and {f.rule for f in fs} == {"GRA003"}
    assert any("NOT aliased" in f.message for f in fs)


@pytest.mark.multichip
def test_undonated_pool_is_gra003():
    """Seeded violation: the round-trip graph forgot donate_argnums —
    every window would copy the pool."""
    import jax

    cell, cfg, pol, factory, jobs, *_ = _tiny_objects(topology="1x1")
    key, fn, args = next(j for j in jobs if j[0] == "splice")
    undonated = jax.jit(factory.traced_splice)   # no donate_argnums
    fs = passes.check_job(cell, cfg, pol, key, undonated, args,
                          compile_jobs=False)
    assert [f.rule for f in fs] == ["GRA003"]
    assert "not donated" in fs[0].message


@pytest.mark.multichip
def test_int8_reaching_matmul_is_gra004():
    """Seeded violation: gathered int8 pool values hit a dot_general
    without dequantization."""
    import jax
    import jax.numpy as jnp

    def bad_gather(pool, row):
        g = pool["k"][row]                       # int8, no dequant
        return jnp.einsum("bd,dk->bk", g, g.T)   # int8 x int8 matmul

    pool = {"k": jax.ShapeDtypeStruct((4, 8, 8), jnp.int8)}
    jaxpr = jax.make_jaxpr(bad_gather)(
        pool, jax.ShapeDtypeStruct((), jnp.int32))
    hits = passes.int8_dot_operands(jaxpr.jaxpr)
    assert len(hits) == 1

    # and through check_job on a quant cell: a splice that leaves the
    # pool bf16 (quantization skipped) is the same boundary leak
    qcell = replace(TINY, kv_quant="int8")
    cell, cfg, pol, factory, jobs, *_ = _tiny_objects(
        topology="2x1", cell=qcell)
    key, fn, args = next(j for j in jobs if j[0] == "splice")
    apool = args[0]
    bf16_pool = {n: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16
                                         if not n.endswith("_scale")
                                         else a.dtype,
                                         sharding=a.sharding)
                 for n, a in apool.items()}
    leaky = jax.jit(lambda pool, k, v, off, phys: pol.constrain_kv(pool),
                    donate_argnums=(0,))
    fs = passes.check_job(cell, cfg, pol, key, leaky,
                          (bf16_pool,) + args[1:], compile_jobs=False)
    assert {f.rule for f in fs} == {"GRA004"}
    assert any("quant boundary" in f.message for f in fs)


@pytest.mark.multichip
def test_open_signature_set_is_gra005():
    """Seeded violation: a verify signature the scheduler can reach but
    precompile never lowered (and the dead-compile inverse)."""
    cell, cfg, pol, factory, jobs, buckets, spec_lens = _tiny_objects()
    have = {k for k, _, _ in jobs}
    fs = passes.signature_findings(cell.name, have - {("verify", 2)},
                                   factory.reachable_keys(buckets, (2,)))
    assert [f.rule for f in fs] == ["GRA005"]
    assert "NOT precompiled" in fs[0].message
    fs = passes.signature_findings(cell.name, have | {("decode", 99)},
                                   factory.reachable_keys(buckets,
                                                          spec_lens))
    assert [f.rule for f in fs] == ["GRA005"]
    assert "not reachable" in fs[0].message


# ---------------------------------------------------------------------------
# the recompile sentinel (satellite: runtime face of GRA005)
# ---------------------------------------------------------------------------

@pytest.mark.multichip
def test_recompile_sentinel_counts_post_seal_misses(caplog):
    import logging

    # a FRESH factory (build_cell does not enumerate jobs, so nothing is
    # cached yet)
    cell = replace(TINY, topology="1x1")
    _cfg, _ecfg, _pol, factory, *_rest = passes.build_cell(cell)
    factory.decode_k(1)
    factory.decode_k(1)                  # cache hit: not a compile
    assert factory.compiles == 1 and factory.post_seal_compiles == 0
    factory.seal()
    with caplog.at_level(logging.WARNING, logger="tpu9.serving"):
        factory.decode_k(7)              # post-warmup miss
    assert factory.post_seal_compiles == 1
    assert any("post-warmup graph compile" in r.message
               for r in caplog.records)


def test_engine_stats_surface_graph_compiles():
    """graph_compiles ride stats() — the pressure heartbeat forwards
    them into /api/v1/metrics engines (same flat-scalar path as the
    topology fields)."""
    import jax
    import jax.numpy as jnp
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.engine import EngineConfig, InferenceEngine

    tiny = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    eng = InferenceEngine(
        init_decoder(jax.random.PRNGKey(0), tiny), tiny,
        EngineConfig(max_batch=2, max_seq_len=128, prefill_buckets=(32,),
                     decode_steps=(1, 2), kv_block_size=32,
                     kv_pool_blocks=8, prefill_chunk=32))
    st = eng.stats()
    assert st["graph_compiles"] == 0
    assert st["graph_compiles_post_warmup"] == 0
    eng.warmup()                          # compiles + seals
    st = eng.stats()
    assert st["graph_compiles"] > 0
    assert st["graph_compiles_post_warmup"] == 0


def test_warmup_covers_every_reachable_signature():
    """The sentinel's contract: after warmup() the executable cache holds
    EVERY reachable key (the dense dsplice gap is closed too)."""
    import jax
    import jax.numpy as jnp
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.engine import EngineConfig, InferenceEngine

    tiny = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), tiny)
    for ecfg in (
        EngineConfig(max_batch=2, max_seq_len=128, prefill_buckets=(32,),
                     decode_steps=(1, 2), kv_block_size=32,
                     kv_pool_blocks=8, prefill_chunk=32, spec_len=2),
        EngineConfig(max_batch=2, max_seq_len=128,
                     prefill_buckets=(32, 64), decode_steps=(1, 2),
                     spec_len=2),        # dense mode
    ):
        eng = InferenceEngine(params, tiny, ecfg)
        eng.warmup()
        missing = eng.graphs.reachable_keys(
            eng._buckets, eng._spec_lens) - set(eng._compiled)
        assert missing == set(), missing


@pytest.mark.multichip
def test_abstract_state_matches_real_engine_arrays():
    """The device-free abstract state graphcheck lowers against must
    mirror the arrays a REAL engine allocates, or the verified graphs
    aren't the served graphs."""
    import jax
    import jax.numpy as jnp
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.engine import EngineConfig, InferenceEngine
    from tpu9.serving.graphs import abstract_state
    from tpu9.serving.shard import make_policy

    tiny = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    ecfg = EngineConfig(max_batch=2, max_seq_len=128,
                        prefill_buckets=(32,), decode_steps=(1, 2),
                        kv_block_size=32, kv_pool_blocks=8,
                        prefill_chunk=32)
    policy = make_policy("2x1")
    eng = InferenceEngine(
        policy.place_params(init_decoder(jax.random.PRNGKey(0), tiny)),
        tiny, ecfg, policy=policy)
    state = abstract_state(tiny, ecfg, policy)
    for name, sds in state["kv_cache"].items():
        assert eng.kv_cache[name].shape == sds.shape, name
        assert eng.kv_cache[name].dtype == sds.dtype, name
    assert state["mb"] == eng._mb
    assert set(state["pool"]) == set(eng._pool_dict())


# ---------------------------------------------------------------------------
# json schema round-trip (satellite: machine-readable findings)
# ---------------------------------------------------------------------------

class TestJsonSchema:
    def test_finding_round_trip(self):
        src = """
        import jax
        def build(fn):
            return jax.jit(fn)
        """
        (f,) = check(src)
        d = finding_json(f, "new")
        assert tuple(d) == JSON_FIELDS
        back = finding_from_json(json.loads(json.dumps(d)))
        assert back.fingerprint == f.fingerprint
        assert (back.rule, back.path, back.line, back.col,
                back.symbol, back.message) == \
            (f.rule, f.path, f.line, f.col, f.symbol, f.message)

    def test_lint_cli_emits_schema(self, tmp_path, capsys):
        from tpu9.analysis.__main__ import main as lint_main
        (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text(
            "import asyncio\n"
            "async def f(sub):\n"
            "    await asyncio.wait_for(sub.get(), 1)\n")
        rc = lint_main(["--repo-root", str(tmp_path), "--format", "json",
                        "pkg"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["version"] == 1 and out["tool"] == "tpu9lint"
        assert [f["rule"] for f in out["findings"]] == ["ASY001"]
        rec = out["findings"][0]
        assert tuple(rec) == JSON_FIELDS
        assert rec["file"] == "pkg/bad.py" and rec["line"] == 3
        assert rec["status"] == "new"
        back = finding_from_json(rec)
        assert back.fingerprint == rec["fingerprint"]


# ---------------------------------------------------------------------------
# boundary edges (satellite: graphcheck is a DECLARED importer)
# ---------------------------------------------------------------------------

def test_graphcheck_boundary_edges_declared_and_live():
    """graphcheck must be declared in the restricted importer lists it
    uses (graphs + shard.policy hooks) and must actually import the hook
    modules (a dead declaration is vacuous) — and NOTHING deeper
    (engine/schedule/kvpool stay closed to it)."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    assert "tpu9.analysis.graphcheck" in \
        cfg.restricted["tpu9.serving.graphs"]
    assert "tpu9.analysis.graphcheck" in \
        cfg.restricted["tpu9.serving.shard.policy"]
    # the [graphcheck] table drives Pass B scope
    assert cfg.graph["jit_owners"] == ["tpu9/serving/graphs.py",
                                       "tpu9/serving/shard/policy.py"]

    gc_dir = os.path.join(REPO, "tpu9", "analysis", "graphcheck")
    imports = set()
    for fn in os.listdir(gc_dir):
        if not fn.endswith(".py"):
            continue
        rel = f"tpu9/analysis/graphcheck/{fn}"
        with open(os.path.join(REPO, rel)) as f:
            tree = ast.parse(f.read())
        imports |= {t for t, _ in bnd.extract_imports(rel, tree)}
    serving = {t for t in imports if t.startswith("tpu9.serving")}
    assert any(t.startswith("tpu9.serving.graphs") for t in serving)
    assert any(t.startswith("tpu9.serving.shard") for t in serving)
    deeper = {t for t in serving
              for mod in ("tpu9.serving.engine", "tpu9.serving.schedule",
                          "tpu9.serving.kvpool")
              if t == mod or t.startswith(mod + ".")}
    assert deeper == set(), f"graphcheck reaches engine internals: {deeper}"


# ---------------------------------------------------------------------------
# the gate (tier-1 wiring)
# ---------------------------------------------------------------------------

def test_find_cells_rejects_unknown():
    with pytest.raises(KeyError, match="unknown graphcheck cell"):
        find_cells(["nope@9x9"])
    assert [c.name for c in find_cells(["llama3-8b@2x1"])] == \
        ["llama3-8b@2x1"]


def test_matrix_covers_flagship_topologies():
    """The ISSUE 11 floor: flagship preset × {1x1, tp=2, 2x2}, plus a
    quantized cell (scale planes) and a dense cell (legacy graphs)."""
    names = {c.name for c in MATRIX}
    assert {"llama3-8b@1x1", "llama3-8b@2x1", "llama3-8b@2x2"} <= names
    assert any(c.kv_quant for c in MATRIX)
    assert any(not c.paged for c in MATRIX)


def test_gate_fails_on_seeded_finding(monkeypatch, capsys):
    """A REAL finding (from the broken-policy fixture class) must fail
    graph_gate with exit 1 — Pass A findings have no baseline."""
    from tpu9.analysis.findings import Finding
    seeded = Finding("GRA002", "graph://fixture@2x1", 0, 0,
                     "KV output `k` is not pinned by constrain_kv",
                     symbol="('decode', 1)")
    monkeypatch.setattr(
        passes, "run_matrix",
        lambda cells, compile_jobs=True: {
            "findings": [seeded], "cells": [], "elapsed_s": 0.0,
            "cpu_s": 0.0})
    rc = graph_gate.main([])
    out = capsys.readouterr().out
    assert rc == 1 and "GRA002" in out and "FAIL" in out


@pytest.mark.multichip
def test_repo_graph_gate_is_green(capsys):
    """THE tier-1 gate: the full preset × topology matrix verifies clean
    on this repo — the findings are judged first, so that a finding is
    never mistaken for a slow machine — inside its runtime budget: 400 s
    of this process's CPU time (about 160 s here; 50-60 s of wall clock
    alone). Wall clock was the budget until ISSUE 34, and tripped under
    six busy xdist workers with 0 findings."""
    rc = graph_gate.main(["--budget-s", "400"])
    out = capsys.readouterr()
    assert f"{len(MATRIX)} cells" in out.out and " 0 findings" in out.out, \
        out.out[-2000:]
    assert rc == 0, out.err[-2000:]


# ---------------------------------------------------------------------------
# no program holds a rope table (PR 60): the benchmark's configurations
# ---------------------------------------------------------------------------

def _benchmark_config_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["configs"]]


def _rehearsal_programs(name: str):
    """``(cfg, {key: lowered text})`` of configuration ``name``'s forward
    programs (decode, chunk, group) at its rehearsal size, built as
    ``benchmark/tools/lowered_configs.py`` builds them."""
    from benchmark import manifest, serve
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard import make_policy
    m = manifest.load()
    config = manifest.load_config(m, name)
    family = manifest.family(config)
    tiny, _ = manifest.module("run").apply_rehearsal(config, name)
    cfg = family.program_config(family.model_sizes(tiny))
    ecfg = serve.engine_config(tiny["engine"])
    policy = make_policy(tiny["engine"]["topology"])
    factory = GraphFactory(cfg, ecfg, policy, chunk=ecfg.prefill_chunk)
    st = abstract_state(cfg, ecfg, policy)
    texts = {}
    for key, fn, args in factory.lowering_jobs(
            abstract_params_for(cfg, False), st["kv_cache"], st["pool"],
            st["scratch"], st["mb"], [ecfg.prefill_chunk], (), st["rng"]):
        if isinstance(key, tuple) and key[0] in ("decode", "chunk",
                                                 "chunkgroup"):
            texts[key] = fn.lower(*args).as_text()
    return cfg, texts


def _table_type(cfg) -> str:
    """The lowered type of a rope table over every position ``cfg``'s model
    could hold: ``max_seq_len`` rows of ``half`` float32 columns."""
    return (f"tensor<{cfg.max_seq_len}x"
            f"{(cfg.mla_rope or cfg.head_dim) // 2}xf32>")


def test_the_table_a_program_must_not_hold_is_told_from_its_text():
    """The control of the test below: a program that builds the table (as
    every program did until PR 60) has the type the test looks for."""
    import jax
    import jax.numpy as jnp
    from tpu9.models.transformer import DecoderConfig
    cfg = DecoderConfig(max_seq_len=2048, head_dim=16)

    def gathers(positions):
        angles = jnp.arange(cfg.max_seq_len, dtype=jnp.float32)[:, None] \
            * jnp.ones(cfg.head_dim // 2, jnp.float32)
        return jnp.sin(angles)[positions]

    text = jax.jit(gathers).lower(jnp.zeros((4, 1), jnp.int32)).as_text()
    assert _table_type(cfg) == "tensor<2048x8xf32>"
    assert _table_type(cfg) in text


@pytest.mark.parametrize("name", _benchmark_config_names())
def test_no_program_holds_a_rope_table(name):
    """A configuration with rotary embeddings takes the sines of the rows
    its program feeds and no tensor of a decode, chunk or group program is a
    table over ``max_seq_len`` positions; one without takes no sine at
    all."""
    cfg, texts = _rehearsal_programs(name)
    assert {key[0] for key in texts} == {"decode", "chunk", "chunkgroup"}
    for key, text in texts.items():
        if cfg.rope:
            assert text.count("stablehlo.sine") > 0, key
            assert text.count(_table_type(cfg)) == 0, key
        else:
            assert text.count("stablehlo.sine") == 0, key
