"""Pass B — AST rules for the sharding/dtype boundary (ISSUE 11).

- **SHD001** ``jax.jit`` opened in a mesh-capable serving module that is
  not a declared jit owner (``[graphcheck] jit_owners`` in
  boundaries.toml) and carries no explicit ``out_shardings``. The engine
  split made ``serving/graphs.py`` the ONLY serving module that traces
  jax; a drive-by jit elsewhere bypasses the sharding policy, the
  executable cache and the recompile sentinel at once.
- **SHD002** use of a donated buffer after the donating call: a name
  bound from ``jax.jit(..., donate_argnums=...)`` is called, and an
  argument passed at a donated position is read again afterwards without
  being rebound. The donated buffer is DEAD after the call — XLA may
  have reused its pages — so that read returns garbage on hardware while
  silently "working" on backends that ignore donation.
- **DTY001** raw int8 KV symbols (``[graphcheck] int8_symbols``, e.g.
  ``quantize_kv``/``dequantize_kv``) imported from ``ops.quant`` by a
  module outside the declared carrier list (``int8_carriers``). This is
  the static face of the BND001 restricted list, one level finer: BND001
  bounds who may import ``tpu9.ops.quant`` at all; DTY001 bounds which
  of those modules may touch the raw int8 payload/scale layout, so the
  dtype-closure invariant Pass A checks per-graph also holds at the
  import graph.

All three are configured from the ``[graphcheck]`` table in
boundaries.toml so scope changes are reviewed edits there, not code
changes here.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from ..findings import Finding
from ..rules import dotted_name

GRAPH_AST_RULES = ("SHD001", "SHD002", "DTY001")

# Defaults mirror boundaries.toml's [graphcheck] table; the toml wins
# when present so the contract stays a reviewed, declarative edit.
DEFAULT_GRAPH_CFG = {
    "mesh_scope": ["tpu9/serving/"],
    "jit_owners": ["tpu9/serving/graphs.py", "tpu9/serving/shard/policy.py"],
    "int8_sources": ["tpu9.ops.quant"],
    "int8_symbols": ["quantize_kv", "dequantize_kv"],
    "int8_carriers": ["tpu9.ops", "tpu9.models.kvstate"],
}


@dataclass
class GraphLintConfig:
    mesh_scope: list = field(
        default_factory=lambda: list(DEFAULT_GRAPH_CFG["mesh_scope"]))
    jit_owners: list = field(
        default_factory=lambda: list(DEFAULT_GRAPH_CFG["jit_owners"]))
    int8_sources: list = field(
        default_factory=lambda: list(DEFAULT_GRAPH_CFG["int8_sources"]))
    int8_symbols: list = field(
        default_factory=lambda: list(DEFAULT_GRAPH_CFG["int8_symbols"]))
    int8_carriers: list = field(
        default_factory=lambda: list(DEFAULT_GRAPH_CFG["int8_carriers"]))

    @classmethod
    def from_dict(cls, raw: dict) -> "GraphLintConfig":
        cfg = cls()
        for key in DEFAULT_GRAPH_CFG:
            if key in raw:
                setattr(cfg, key, list(raw[key]))
        return cfg


def _in_scope(path: str, prefixes) -> bool:
    return any(path == p.rstrip("/") or path.startswith(p)
               for p in prefixes)


def _module_prefix(mod: str, prefixes) -> bool:
    return any(mod == p or mod.startswith(p + ".") for p in prefixes)


# -- SHD001 -------------------------------------------------------------------

def _check_jit_ownership(path: str, tree: ast.AST,
                         cfg: GraphLintConfig) -> list[Finding]:
    if not _in_scope(path, cfg.mesh_scope) or path in cfg.jit_owners:
        return []
    findings: list[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name not in ("jax.jit", "jit"):
            continue
        if any(kw.arg == "out_shardings" for kw in node.keywords):
            # an explicit layout contract is the one sanctioned reason to
            # jit outside the factory (the policy's sharded-zeros builder)
            continue
        findings.append(Finding(
            "SHD001", path, node.lineno, node.col_offset,
            f"`{name}` opened outside the GraphFactory (declared jit "
            f"owners: {cfg.jit_owners}) without explicit out_shardings: "
            "serving graphs must trace through serving/graphs.py so the "
            "sharding policy, executable cache and recompile sentinel "
            "all apply", symbol=name))
    return findings


# -- SHD002 -------------------------------------------------------------------

def _donated_positions(call: ast.Call):
    """Literal donate_argnums of a ``jax.jit(...)`` call, or None."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if not (isinstance(e, ast.Constant)
                        and isinstance(e.value, int)):
                    return None          # non-literal: can't reason
                out.append(e.value)
            return tuple(out)
        return None
    return None


def _check_donated_reuse(path: str, tree: ast.AST) -> list[Finding]:
    """Per-scope linear scan: find names bound from donating jits, then
    flag any read of a buffer passed at a donated position after the
    donating call, unless the name was rebound in between (including by
    the call's own result assignment, the round-trip idiom)."""
    findings: list[Finding] = []

    def scan_scope(owner: ast.AST) -> None:
        # nested function bodies are their own scopes
        nested: set[int] = set()
        for c in ast.walk(owner):
            if (isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)) and c is not owner
                    and id(c) not in nested):
                nested.update(id(x) for x in ast.walk(c))
        own = [n for n in ast.walk(owner) if id(n) not in nested]

        jits: dict[str, tuple] = {}
        for n in own:
            if not (isinstance(n, ast.Assign)
                    and isinstance(n.value, ast.Call)
                    and dotted_name(n.value.func) in ("jax.jit", "jit")):
                continue
            donated = _donated_positions(n.value)
            if donated is None:
                continue
            for tgt in n.targets:
                tname = dotted_name(tgt)
                if tname:
                    jits[tname] = donated
        if not jits:
            return

        pos = lambda n: (n.lineno, n.col_offset)  # noqa: E731
        # result-target names per donating call: `tok, kv = f(...)`
        # rebinds kv AFTER the RHS runs, even though the target's
        # position precedes the call's
        result_names: dict[int, set] = {}
        for n in own:
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                names = set()
                for tgt in n.targets:
                    for sub in ast.walk(tgt):
                        nm = dotted_name(sub)
                        if nm:
                            names.add(nm)
                result_names[id(n.value)] = names

        # (position, donating-call node, buffer name) per donated arg
        dead: list[tuple] = []
        stores: list[tuple] = []
        loads: list[tuple] = []
        for n in own:
            if isinstance(n, ast.Call):
                fname = dotted_name(n.func)
                if fname in jits:
                    inside = {id(x) for x in ast.walk(n)}
                    for i in jits[fname]:
                        if i < len(n.args):
                            buf = dotted_name(n.args[i])
                            if buf:
                                dead.append((pos(n), n, buf, inside))
            if isinstance(n, (ast.Name, ast.Attribute)):
                nm = dotted_name(n)
                if not nm:
                    continue
                ctx = getattr(n, "ctx", None)
                if isinstance(ctx, ast.Store):
                    stores.append((pos(n), nm))
                elif isinstance(ctx, ast.Load):
                    loads.append((pos(n), nm, id(n)))

        for dpos, call, buf, inside in dead:
            if buf in result_names.get(id(call), ()):
                continue                 # round-trip idiom: rebound by
                                         # the donating call's own result
            rebinds = [p for p, nm in stores if nm == buf and p > dpos]
            for lpos, nm, nid in sorted(loads):
                if nm != buf or lpos <= dpos or nid in inside:
                    continue
                if any(rp <= lpos for rp in rebinds):
                    break                # rebound: later reads are fine
                findings.append(Finding(
                    "SHD002", path, lpos[0], lpos[1],
                    f"`{buf}` is read after being DONATED to "
                    f"`{dotted_name(call.func)}` (line {dpos[0]}): the "
                    "buffer is dead after the call — XLA may alias its "
                    "pages into the output — so this read returns "
                    "garbage on hardware; rebind the name from the "
                    "call's result (the round-trip idiom) or drop the "
                    "donation", symbol=buf))
                break                    # one finding per donated buffer
    scan_scope(tree)
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_scope(n)
    return findings


# -- DTY001 -------------------------------------------------------------------

def _check_int8_escape(path: str, tree: ast.AST,
                       cfg: GraphLintConfig) -> list[Finding]:
    mod = path[:-3].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    if _module_prefix(mod, cfg.int8_carriers):
        return []
    findings: list[Finding] = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.ImportFrom):
            continue
        if n.level:
            parts = mod.split(".")
            if not path.endswith("__init__.py"):
                parts = parts[:-1]
            parts = parts[: len(parts) - n.level + 1]
            base = ".".join(parts)
            target = f"{base}.{n.module}" if n.module else base
        else:
            target = n.module or ""
        if target not in cfg.int8_sources:
            continue
        for a in n.names:
            if a.name in cfg.int8_symbols:
                findings.append(Finding(
                    "DTY001", path, n.lineno, n.col_offset,
                    f"`{mod}` imports raw int8 KV symbol `{a.name}` from "
                    f"`{target}`: only the declared int8 carriers "
                    f"{cfg.int8_carriers} (boundaries.toml [graphcheck]) "
                    "may touch the payload/scale layout — everything "
                    "else must see KV through the dequantizing readers",
                    symbol=a.name))
    return findings


def check_graph_file(path: str, tree: ast.AST,
                     cfg: GraphLintConfig | None = None) -> list[Finding]:
    cfg = cfg or GraphLintConfig()
    findings = _check_jit_ownership(path, tree, cfg)
    findings += _check_donated_reuse(path, tree)
    findings += _check_int8_escape(path, tree, cfg)
    return findings
