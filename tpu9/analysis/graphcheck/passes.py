"""Pass A — abstract lowering + invariant verification (ISSUE 11).

For each matrix cell this module builds the EXACT objects the serving
engine would build — DecoderConfig, EngineConfig, sharding policy,
GraphFactory — hands the factory abstract (``ShapeDtypeStruct``) state,
and verifies every graph the factory enumerates, without allocating a
buffer or touching a device:

- **GRA001** weight sharding: under tp>1 every weight leaf the layout
  rule declares sharded must RESOLVE sharded (the divisibility fallback
  silently replicates otherwise — all the HBM, none of the capacity),
  at least one tp-sharded matmul operand must exist per cell, and the
  compiled executable's input shardings must match the policy's resolved
  specs leaf-for-leaf.
- **GRA002** KV constraint: every KV-state output of every graph must be
  produced by ``sharding_constraint`` carrying the policy's declared
  head-axis spec, and the compiled output shardings must keep the head
  axis — so a donation round-trip can never hand GSPMD an excuse to
  gather the pool. On 1x1 the SAME check inverts: no constraint op may
  exist at all (the bit-identical single-device graph contract).
- **GRA003** donation: the pool/cache/scratch argument of every
  round-trip graph must be declared donated, and every donated leaf must
  be genuinely aliased in the compiled executable
  (``input_output_alias``) — a dropped alias is a silent full-pool copy
  per window.
- **GRA004** dtype closure: no ``dot_general`` anywhere in the jaxpr
  (scan bodies included) takes an int8 operand; scratch/gather outputs
  stay the model dtype; on an int8 pool the payload leaves stay int8 and
  the scale planes f32 through every writer.
- **GRA005** closed signatures: the factory's ``lowering_jobs`` key set
  equals its ``reachable_keys`` set — steady-state serving provably
  cannot hit an uncompiled executable-cache key.
"""

from __future__ import annotations

import re
import time
from dataclasses import replace
from typing import Any, Optional

from ..findings import Finding
from .matrix import MATRIX, Cell

# expected donation per graph kind: (argument index, human name). The
# pool/cache/scratch round-trip buffers MUST be donated — an undonated
# pool doubles HBM traffic per window.
EXPECTED_DONATION = {
    "decode": ((1, "kv_cache"),),
    "verify": ((1, "kv_cache"),),
    "chunk": ((3, "scratch"),),
    "splice": ((0, "pool"),),
    "chunkgroup": ((1, "pool"), (2, "scratch")),
    "dsplice": ((0, "cache k"), (1, "cache v")),
    "prefill": (),
    "gather": (),
}

KV_NAMES = ("k", "v", "k_scale", "v_scale", "table")

# graph kinds whose argument 0 is the weight tree (GRA001's subject);
# the splice/gather/dsplice plumbing graphs take only KV state
PARAMS_KINDS = ("decode", "verify", "chunk", "chunkgroup", "prefill")


def _aliased_params(hlo_text: str) -> set:
    """Entry-parameter numbers aliased to an output in a compiled HLO
    module's ``input_output_alias={ {out}: (param, {}, kind), ... }``
    header (brace-balanced scan — entries nest braces)."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return set()
    i = start + len("input_output_alias=")
    depth = 0
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo_text[i:j + 1]
    return {int(p) for p in re.findall(r"\}:\s*\((\d+)\s*,", body)}


def kind_of(key) -> str:
    if isinstance(key, tuple):
        return key[0]
    return "prefill" if isinstance(key, int) else key


def _f(rule: str, cell_name: str, key, message: str) -> Finding:
    return Finding(rule, f"graph://{cell_name}", 0, 0, message,
                   symbol=str(key))


# -- cell construction --------------------------------------------------------

def build_cell(cell: Cell):
    """(cfg, ecfg, policy, factory, params, state, buckets, spec_lens) —
    the exact objects an engine of this cell would hold, all abstract."""
    from tpu9.serving import EngineConfig
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for, resolve_preset
    from tpu9.serving.shard import make_policy

    cfg, quantized = resolve_preset(cell.preset, cell.quantize or None)
    cfg = replace(cfg, n_layers=cell.n_layers,
                  loop_steps=cell.loop_steps or cfg.loop_steps)
    ecfg = EngineConfig(
        max_batch=cell.max_batch, max_seq_len=cell.max_seq_len,
        prefill_buckets=(cell.prefill_buckets if not cell.paged
                         else (cell.chunk, cell.max_seq_len)),
        decode_steps=cell.decode_steps,
        kv_block_size=cell.kv_block_size if cell.paged else 0,
        kv_pool_blocks=cell.kv_pool_blocks,
        prefill_chunk=cell.chunk if cell.paged else 0,
        spec_len=cell.spec_len,
        kv_quant=cell.kv_quant,
        admit_group_chunks=cell.admit_group_chunks)
    policy = make_policy(cell.topology)
    params = abstract_params_for(cfg, quantized)
    state = abstract_state(cfg, ecfg, policy, kv_quant=bool(cell.kv_quant))
    # the engine's own bucket clamping (_bucket_for)
    buckets = sorted({min(bk, ecfg.max_seq_len)
                      for bk in ecfg.prefill_buckets})
    spec_lens = (ecfg.spec_len,) if ecfg.spec_len > 0 else ()
    factory = GraphFactory(cfg, ecfg, policy,
                           chunk=cell.chunk if cell.paged else 0,
                           kv_quant=bool(cell.kv_quant))
    return cfg, ecfg, policy, factory, params, state, buckets, spec_lens


# -- jaxpr helpers ------------------------------------------------------------

def _producer(jaxpr, var):
    """The eqn producing ``var`` in this jaxpr, or None (invar/literal)."""
    for eqn in jaxpr.eqns:
        if any(v is var for v in eqn.outvars):
            return eqn
    return None


def constraint_for_output(jaxpr, var):
    """The ``sharding_constraint`` sharding pinning ``var`` (every graph
    pins its KV outputs at its own top level or inside a jitted helper,
    never inside a loop body). None when the output is unpinned."""
    eqn = _producer(jaxpr, var)
    if eqn is None:
        return None
    name = eqn.primitive.name
    if name == "sharding_constraint":
        return eqn.params.get("sharding")
    if name == "pjit":
        idx = next(i for i, v in enumerate(eqn.outvars) if v is var)
        body = eqn.params["jaxpr"].jaxpr
        return constraint_for_output(body, body.outvars[idx])
    return None


def walk_eqns(jaxpr):
    """Every eqn, recursing into sub-jaxprs (scan/pjit/cond bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from walk_eqns(sub)


def _sub_jaxprs(v):
    from jax.extend import core
    if isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for e in v:
            yield from _sub_jaxprs(e)


def has_sharding_constraint(jaxpr) -> bool:
    return any(e.primitive.name == "sharding_constraint"
               for e in walk_eqns(jaxpr))


def int8_dot_operands(jaxpr) -> list:
    """(eqn, operand-dtypes) for every dot_general with an int8 operand."""
    import numpy as np
    hits = []
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        dts = [getattr(v.aval, "dtype", None) for v in eqn.invars[:2]]
        if any(dt is not None and np.dtype(dt) == np.dtype("int8")
               for dt in dts):
            hits.append((eqn, dts))
    return hits


# -- output classification ----------------------------------------------------

def kv_out_leaves(key, out_sds) -> list:
    """[(flat_index, kv_name, aval)] for the KV-state leaves of a graph's
    output tree. Dict-keyed leaves classify by their final key; the dense
    splice returns bare ``(k, v)`` positionally."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(out_sds)[0]
    if kind_of(key) == "dsplice":
        return [(i, ("k", "v")[i], leaf) for i, (_, leaf)
                in enumerate(leaves)]
    out = []
    for i, (path, leaf) in enumerate(leaves):
        last = path[-1] if path else None
        name = getattr(last, "key", None)
        if name in KV_NAMES:
            out.append((i, name, leaf))
    return out


def _spec_axes(spec) -> tuple:
    """Normalized per-dim axis tuples of a PartitionSpec (None-padded
    entries dropped from the tail)."""
    if spec is None:
        return ()
    norm = []
    for e in spec:
        if e is None:
            norm.append(())
        elif isinstance(e, (tuple, list)):
            norm.append(tuple(e))
        else:
            norm.append((e,))
    while norm and norm[-1] == ():
        norm.pop()
    return tuple(norm)


# -- per-job verification -----------------------------------------------------

def check_job(cell: Cell, cfg, policy, key, fn, args,
              compile_jobs: bool = True) -> list:
    import jax
    import jax.numpy as jnp

    findings: list[Finding] = []
    is_mesh = policy.mesh is not None
    kind = kind_of(key)

    traced = fn.trace(*args)
    jaxpr = traced.jaxpr.jaxpr           # the graph body
    # out_info is the output pytree of shape/dtype leaves from the SAME
    # trace — eval_shape here would re-trace the whole decoder per job
    out_sds = traced.out_info
    kv_outs = kv_out_leaves(key, out_sds)

    # ---- GRA002: constrain_kv on every KV output ----
    if is_mesh:
        for i, name, leaf in kv_outs:
            sharding = constraint_for_output(jaxpr, jaxpr.outvars[i])
            if sharding is None:
                findings.append(_f(
                    "GRA002", cell.name, key,
                    f"KV output `{name}` is not pinned by constrain_kv: "
                    "a donation round-trip may let GSPMD gather or "
                    "re-layout the pool mid-serve"))
                continue
            want = _spec_axes(policy.kv_spec(name, len(leaf.shape)))
            got = _spec_axes(getattr(sharding, "spec", None))
            if got != want:
                findings.append(_f(
                    "GRA002", cell.name, key,
                    f"KV output `{name}` constrained to {got}, policy "
                    f"declares {want}: the pool would resettle into a "
                    "different layout than admission/decode write through"))
    elif has_sharding_constraint(jaxpr):
        findings.append(_f(
            "GRA002", cell.name, key,
            "sharding_constraint in a SINGLE-DEVICE graph: the 1x1 "
            "policy must trace bit-identical graphs to the pre-split "
            "engine (identity hooks only)"))

    # ---- GRA003: donation declared ----
    # Traced.donate_argnums reports FLAT leaf indices; map each expected
    # top-level argument to its flat span
    import jax.tree_util as jtu
    counts = [len(jtu.tree_flatten(a)[0]) for a in args]
    starts = [sum(counts[:i]) for i in range(len(args))]
    donated = set(traced.donate_argnums or ())
    for argpos, what in EXPECTED_DONATION.get(kind, ()):
        span = set(range(starts[argpos], starts[argpos] + counts[argpos]))
        if not span <= donated:
            findings.append(_f(
                "GRA003", cell.name, key,
                f"{what} (arg {argpos}) is not donated: every window "
                "would copy the full buffer instead of aliasing it"))

    # ---- GRA004: dtype closure ----
    for eqn, dts in int8_dot_operands(jaxpr):
        findings.append(_f(
            "GRA004", cell.name, key,
            f"dot_general with int8 operand(s) {dts}: int8 storage "
            "reached a matmul undequantized — values are missing their "
            "scales"))
    # scratch/gather outputs stay model dtype; pool payload stays the
    # pool dtype (int8 under kv_quant — the write really quantized);
    # scale planes stay f32
    model_dt = jnp.dtype(cfg.dtype)
    pool_dt = jnp.dtype(jnp.int8) if cell.kv_quant else model_dt
    for i, name, leaf in kv_outs:
        dt = jnp.dtype(leaf.dtype)
        if name == "table":
            continue
        if name.endswith("_scale"):
            if dt != jnp.dtype(jnp.float32):
                findings.append(_f(
                    "GRA004", cell.name, key,
                    f"scale plane `{name}` left the graph as {dt}, "
                    "expected float32"))
            continue
        want = pool_dt if _leaf_is_pool(kind, out_sds, i) else model_dt
        if dt != want:
            findings.append(_f(
                "GRA004", cell.name, key,
                f"KV output `{name}` left the graph as {dt}, expected "
                f"{want} ({'pool storage' if want == pool_dt else 'model'}"
                " dtype) — the quant boundary leaked"))

    # ---- compiled-artifact checks ----
    if compile_jobs:
        compiled = traced.lower().compile()
        findings += _check_compiled(cell, policy, key, args, compiled,
                                    donated, kv_outs, out_sds)
    return findings


def _leaf_is_pool(kind: str, out_sds, flat_index: int) -> bool:
    """Whether KV output ``flat_index`` is POOL storage (carries the pool
    dtype — int8 under kv_quant) rather than scratch/dense-cache state
    (always the model dtype). Positional, by graph kind: splice returns
    the pool; chunkgroup returns (pool, scratch, last); decode/verify
    round-trip the engine cache (the pool in paged mode)."""
    import jax
    if kind in ("splice", "decode", "verify"):
        return True
    if kind == "chunkgroup":
        # output element 0 is the pool dict; find the flat span of it
        leaves0 = jax.tree_util.tree_flatten(out_sds[0])[0]
        return flat_index < len(leaves0)
    return False


def _check_compiled(cell, policy, key, args, compiled, donated, kv_outs,
                    out_sds) -> list:
    import jax
    findings: list[Finding] = []
    is_mesh = policy.mesh is not None

    # GRA003: every donated leaf genuinely aliased in the executable.
    # donate_argnums and input_output_alias live in DIFFERENT index
    # spaces: donation indexes the traced flat leaves, the alias map
    # indexes HLO entry parameters, and jit DROPS unused leaves from the
    # entry signature (keep_unused=False default) — so translate through
    # the executable's kept-variable set before comparing.
    donated_flat = set(donated)          # traced flat leaf indices
    aliased_params = _aliased_params(compiled.as_text())
    kept = _kept_var_idx(compiled)
    if kept is None:
        n_flat = sum(len(jax.tree_util.tree_flatten(a)[0]) for a in args)
        if _entry_param_count(compiled.as_text()) == n_flat:
            kept = list(range(n_flat))   # nothing dropped: identity map
    if kept is None:
        findings.append(_f(
            "GRA003", cell.name, key,
            "cannot verify donation aliasing: jit dropped unused "
            "argument leaves and the executable exposes no kept-variable "
            "mapping on this jax version — make every argument used or "
            "extend _kept_var_idx"))
    else:
        aliased_flat = {kept[p] for p in aliased_params
                        if p < len(kept)}
        for idx in sorted(donated_flat & set(kept) - aliased_flat):
            findings.append(_f(
                "GRA003", cell.name, key,
                f"donated input leaf {idx} is NOT aliased in the "
                "compiled executable (input_output_alias) — XLA dropped "
                "the donation (shape/dtype/layout mismatch with every "
                "output), so the round-trip silently copies the buffer "
                "every window"))

    if not is_mesh:
        return findings

    if kind_of(key) in PARAMS_KINDS:
        findings += _check_weight_shardings(cell, policy, key, args,
                                            compiled)

    # GRA002 (compiled face): pool payload outputs keep the head axis —
    # for EVERY mesh graph (the splice/gather plumbing round-trips the
    # pool without taking weights at all)
    import jax.tree_util as jtu
    out_sh = jtu.tree_flatten(compiled.output_shardings)[0]
    for i, name, leaf in kv_outs:
        if name == "table" or name.endswith("_scale"):
            continue
        want = _spec_axes(policy.kv_spec(name, len(leaf.shape)))
        if not want:
            continue
        got_sp = _spec_axes(getattr(out_sh[i], "spec", None))
        if got_sp != want:
            findings.append(_f(
                "GRA002", cell.name, key,
                f"compiled output sharding of `{name}` is {got_sp}, "
                f"policy pins {want}: GSPMD resettled the pool across "
                "the donation round-trip"))
    return findings


def _check_weight_shardings(cell, policy, key, args, compiled) -> list:
    """GRA001: weight leaves carry the policy's resolved specs
    end-to-end (declared-vs-resolved replication + compiled input
    shardings leaf-match). Mesh cells, params-taking graphs only."""
    import jax.tree_util as jtu
    from jax.sharding import PartitionSpec as P

    findings: list[Finding] = []
    params_sds = args[0]
    declared, resolved = policy.param_specs(params_sds)
    in_sh = compiled.input_shardings[0][0]   # the params arg subtree
    is_leaf = lambda x: isinstance(x, P)  # noqa: E731
    decl = jtu.tree_flatten_with_path(declared, is_leaf=is_leaf)[0]
    reso = jtu.tree_flatten(resolved, is_leaf=is_leaf)[0]
    got = jtu.tree_flatten(in_sh)[0]
    mesh_axes = {n for n, s in policy.mesh.shape.items() if s > 1}
    any_tp = False
    for (path, dspec), rspec, sh in zip(decl, reso, got):
        label = jtu.keystr(path)
        d_ax = {a for dim in _spec_axes(dspec) for a in dim}
        r_ax = {a for dim in _spec_axes(rspec) for a in dim}
        if "tp" in r_ax & mesh_axes:
            any_tp = True
        if d_ax & mesh_axes and not r_ax & mesh_axes:
            findings.append(_f(
                "GRA001", cell.name, key,
                f"weight leaf {label} declared {_spec_axes(dspec)} but "
                f"resolved REPLICATED (divisibility fallback): every "
                "chip holds the full tensor — all the HBM, none of the "
                "capacity"))
        actual = _spec_axes(getattr(sh, "spec", None))
        if actual != _spec_axes(rspec):
            findings.append(_f(
                "GRA001", cell.name, key,
                f"weight leaf {label} lowered with sharding {actual}, "
                f"policy resolved {_spec_axes(rspec)}: the executable "
                "will not run on the layout the policy places"))
    if "tp" in mesh_axes and not any_tp and decl:
        findings.append(_f(
            "GRA001", cell.name, key,
            "no tp-sharded weight leaf under tp>1: the decoder layout "
            "rule did not match this param tree — every matmul operand "
            "is replicated"))
    return findings


def _kept_var_idx(compiled):
    """Sorted kept-flat-leaf indices of a compiled executable (jit drops
    unused leaves from the HLO entry signature; HLO parameter N is flat
    leaf kept[N]). None when this jax version doesn't expose it."""
    ex = getattr(compiled, "_executable", None)
    kept = getattr(ex, "_kept_var_idx", getattr(ex, "kept_var_idx", None))
    if kept is None:
        return None
    return sorted(kept)


def _entry_param_count(hlo_text: str):
    """Number of entry parameters in a compiled HLO module, from the
    entry_computation_layout header; None when unparseable."""
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo_text,
                  re.DOTALL)
    if not m:
        return None
    body = m.group(1).strip()
    if not body:
        return 0
    depth, count = 0, 1
    for ch in body:                      # commas inside shapes don't
        if ch in "[{(":                  # separate parameters
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            count += 1
    return count


# -- the matrix runner --------------------------------------------------------

def signature_findings(cell_name: str, have: set, want: set) -> list:
    """GRA005: the precompiled signature set (``have`` — the factory's
    lowering_jobs keys) must equal the serve-loop-reachable set
    (``want`` — reachable_keys). Asymmetric messages: an unprecompiled
    reachable key is a mid-serve stall, a dead precompile is boot-time
    waste or a stale dispatch-site enumeration."""
    findings: list[Finding] = []
    for k in sorted(map(str, want - have)):
        findings.append(_f(
            "GRA005", cell_name, k,
            "signature reachable from the WindowScheduler but NOT "
            "precompiled: the first request hitting it stalls every "
            "stream behind a mid-serve XLA compile"))
    for k in sorted(map(str, have - want)):
        findings.append(_f(
            "GRA005", cell_name, k,
            "signature precompiled but not reachable from the serve "
            "loop: dead boot-time compile (or reachable_keys is stale — "
            "update the dispatch-site enumeration)"))
    return findings


def run_cell(cell: Cell, compile_jobs: bool = True) -> tuple:
    """(findings, stats) for one cell."""
    t0 = time.perf_counter()
    (cfg, ecfg, policy, factory, params, state, buckets,
     spec_lens) = build_cell(cell)
    jobs = list(factory.lowering_jobs(
        params, state["kv_cache"], state["pool"], state["scratch"],
        state["mb"], buckets, spec_lens, state["rng"]))

    # GRA005: the job keys ARE the precompile set; they must equal the
    # serve loop's reachable set exactly
    have = {k for k, _, _ in jobs}
    want = factory.reachable_keys(buckets, spec_lens)
    findings: list[Finding] = signature_findings(cell.name, have, want)

    for key, fn, args in jobs:
        findings.extend(check_job(cell, cfg, policy, key, fn, args,
                                  compile_jobs=compile_jobs))
    stats = {"cell": cell.name, "jobs": len(jobs),
             "elapsed_s": round(time.perf_counter() - t0, 3)}
    return findings, stats


def run_matrix(cells: Optional[list] = None,
               compile_jobs: bool = True) -> dict:
    """Run Pass A over the matrix. Returns ``{"findings": [...],
    "cells": [stats...], "elapsed_s": float, "cpu_s": float}``:
    ``elapsed_s`` is wall clock, ``cpu_s`` this process's own CPU time
    (all threads) — what the matrix costs whoever else is busy on the
    machine, and so what a budget can be held to under a parallel test
    run."""
    t0, c0 = time.perf_counter(), time.process_time()
    cells = cells if cells is not None else list(MATRIX)
    findings: list[Finding] = []
    stats = []
    for cell in cells:
        f, s = run_cell(cell, compile_jobs=compile_jobs)
        findings.extend(f)
        stats.append(s)
    return {"findings": findings, "cells": stats,
            "elapsed_s": round(time.perf_counter() - t0, 3),
            "cpu_s": round(time.process_time() - c0, 3)}


def device_guard(min_devices: int = 8) -> Optional[str]:
    """None when the forced CPU mesh is usable; otherwise the loud
    skip-with-recipe string (mirrors the multichip conftest marker: a
    caller-pinned XLA_FLAGS wins over our forcing, and silently passing
    with 1 device would claim coverage that never ran)."""
    import jax
    n = jax.device_count()
    if n >= min_devices:
        return None
    return (f"graphcheck needs {min_devices} virtual CPU devices for the "
            f"topology matrix, have {n} — re-run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 (or "
            "unset XLA_FLAGS and let the graphcheck CLI force it)")
