"""wirecheck rules (ISSUE 18): contracts.toml vs the extracted surfaces.

Five rules over four wire surfaces:

- WIR001 — stats/heartbeat field agreement per declared surface: phantom
  consumer reads (error), contract entries nothing produces (error),
  producer writes the contract does not know (error), and
  produced-but-never-consumed dead telemetry (warn tier).
- WIR002 — ``tpu9_*`` metric names: asserted-but-never-emitted drift
  (error), per-replica gauge families without ``remove_gauge`` coverage
  (error — the PR 14 unbounded-cardinality class), emitted-but-never-
  asserted (warn tier).
- KEY001 — store key namespaces: undeclared namespace (error),
  cross-plane writes (error), plain ``set`` on an atomic namespace
  (error — the postmortem RMW class), TTL-less writes where the
  namespace requires TTL discipline (error).
- ENV001 — ``TPU9_*`` env reads: undeclared var (error), reader outside
  the declared set (error), divergent inline defaults (error).
- RPC001 — route agreement: registered-but-never-called (error unless
  declared external), called-but-never-registered (error).

Errors gate; warns report. Both carry the shared finding schema.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..findings import Finding
from . import extract as ex


@dataclass
class SurfaceSpec:
    name: str
    fields: list = field(default_factory=list)
    families: list = field(default_factory=list)
    synthetic: list = field(default_factory=list)
    dead_ok: dict = field(default_factory=dict)        # key -> reason
    manual_consumed: dict = field(default_factory=dict)
    producers: list = field(default_factory=list)      # (path, qual, var)
    consumers: list = field(default_factory=list)
    consumer_lists: list = field(default_factory=list)  # (path, const)


@dataclass
class KeySpec:
    name: str
    pattern: str
    writers: list = field(default_factory=list)
    ttl: str = "optional"          # "required" | "optional" | "none"
    atomic: bool = False


@dataclass
class WireContracts:
    surfaces: list = field(default_factory=list)
    keys: list = field(default_factory=list)
    env: dict = field(default_factory=dict)      # var -> [reader prefixes]
    env_divergent_ok: dict = field(default_factory=dict)
    metric_entity_labels: list = field(default_factory=list)
    metric_assert_ok: dict = field(default_factory=dict)
    metric_remove_ok: dict = field(default_factory=dict)
    metric_dynamic_prefixes: list = field(default_factory=list)
    rpc_external_ok: dict = field(default_factory=dict)
    rpc_call_only_ok: dict = field(default_factory=dict)

    @classmethod
    def load(cls, path: str) -> "WireContracts":
        from .. import tomlmini
        raw = tomlmini.load_file(path)
        c = cls()
        for name, t in raw.get("surface", {}).items():
            s = SurfaceSpec(name=name)
            s.fields = list(t.get("fields", []))
            s.families = list(t.get("families", []))
            s.synthetic = list(t.get("synthetic", []))
            s.dead_ok = _reasons(t.get("dead_ok", []))
            s.manual_consumed = _reasons(t.get("manual_consumed", []))
            s.producers = [_scope3(e) for e in t.get("producers", [])]
            s.consumers = [_scope3(e) for e in t.get("consumers", [])]
            s.consumer_lists = [_scope2(e)
                                for e in t.get("consumer_lists", [])]
            c.surfaces.append(s)
        for name, t in raw.get("keys", {}).items():
            c.keys.append(KeySpec(
                name=name, pattern=t.get("pattern", name + ":*"),
                writers=list(t.get("writers", [])),
                ttl=t.get("ttl", "optional"),
                atomic=bool(t.get("atomic", False))))
        for var, t in raw.get("env", {}).items():
            c.env[var] = list(t.get("readers", []))
            if t.get("divergent_ok"):
                c.env_divergent_ok[var] = t["divergent_ok"]
        m = raw.get("metrics", {})
        c.metric_entity_labels = list(m.get("entity_labels", []))
        c.metric_assert_ok = _reasons(m.get("assert_ok", []))
        c.metric_remove_ok = _reasons(m.get("remove_ok", []))
        c.metric_dynamic_prefixes = list(m.get("dynamic_prefixes", []))
        r = raw.get("rpc", {})
        c.rpc_external_ok = _reasons(r.get("external_ok", []))
        c.rpc_call_only_ok = _reasons(r.get("call_only_ok", []))
        return c


def _reasons(entries) -> dict:
    """``["name: why", ...]`` -> {name: why}; a missing reason is an
    authoring error surfaced loudly at load."""
    out = {}
    for e in entries:
        name, _, reason = e.partition(":")
        if not reason.strip():
            raise ValueError(
                f"contracts.toml exemption {e!r} has no reason — every "
                "allowance must say why (\"name: reason\")")
        out[name.strip()] = reason.strip()
    return out


def _scope3(entry: str):
    parts = entry.split("::")
    if len(parts) != 3:
        raise ValueError(
            f"contracts.toml scope {entry!r} must be path::qualname::var")
    return tuple(parts)


def _scope2(entry: str):
    parts = entry.split("::")
    if len(parts) != 2:
        raise ValueError(
            f"contracts.toml list-consumer {entry!r} must be path::CONST")
    return tuple(parts)


# marker for fixture-corpus files (must appear in the first 2 KiB)
FIXTURE_PRAGMA = "tpu9: wirecheck-fixture-corpus"


class CheckContext:
    """One repo scan shared by every rule: per-file module indexes plus
    the global metric/store/env/route inventories."""

    def __init__(self, repo_root: str, contracts: WireContracts,
                 contracts_path: str):
        self.repo_root = repo_root
        self.contracts = contracts
        self.contracts_path = contracts_path
        # findings anchor to the repo-relative path so fingerprints are
        # stable across checkouts
        rel = os.path.relpath(contracts_path, repo_root)
        self.contracts_rel = rel.replace(os.sep, "/")
        self.indexes: dict[str, ex.ModuleIndex] = {}
        self.parse_errors: list[str] = []
        self.metric_emits: list[ex.MetricUse] = []
        self.metric_removes: list[ex.MetricUse] = []
        self.metric_asserts: list[ex.MetricUse] = []
        self.store_ops: list[ex.StoreOp] = []
        self.env_reads: list[ex.EnvRead] = []
        self.routes_registered: list[ex.RouteUse] = []
        self.route_calls: list[ex.RouteUse] = []

    # role predicates — which inventory a file feeds
    @staticmethod
    def _is_test(path: str) -> bool:
        return path.startswith("tests/")

    def _fixture_corpus(self, rel: str) -> bool:
        """Files that opt out of inventory extraction entirely: their
        strings are *about* wire surfaces (checker fixtures, seeded
        violations), not uses of them."""
        try:
            with open(os.path.join(self.repo_root, rel),
                      encoding="utf-8") as fh:
                head = fh.read(2048)
        except OSError:
            return False
        return FIXTURE_PRAGMA in head

    @staticmethod
    def _asserts_metrics(path: str) -> bool:
        return (path.startswith("tests/") or path.startswith("tpu9/cli/")
                or path.startswith("scripts/"))

    def index(self, rel_path: str) -> "ex.ModuleIndex | None":
        idx = self.indexes.get(rel_path)
        if idx is None and rel_path not in self.parse_errors:
            idx = ex.index_module(self.repo_root, rel_path)
            if idx is None:
                self.parse_errors.append(rel_path)
                return None
            self.indexes[rel_path] = idx
        return idx

    def scan(self, rel_paths: list[str]) -> None:
        for rel in rel_paths:
            if self._fixture_corpus(rel):
                continue
            idx = self.index(rel)
            if idx is None:
                continue
            if rel.startswith("tpu9/"):
                for use in ex.extract_metrics(idx):
                    (self.metric_removes if use.method == "remove_gauge"
                     else self.metric_emits).append(use)
                self.store_ops.extend(ex.extract_store_ops(idx))
                if not rel.startswith("tpu9/analysis/"):
                    # the checker's own sources mention route prefixes as
                    # data, not as calls
                    reg, called = ex.extract_routes(idx)
                    self.routes_registered.extend(reg)
                    self.route_calls.extend(called)
            else:
                _, called = ex.extract_routes(idx)
                self.route_calls.extend(called)
            if self._asserts_metrics(rel):
                self.metric_asserts.extend(ex.extract_metric_literals(idx))
            if not self._is_test(rel):
                self.env_reads.extend(ex.extract_env_reads(idx))

    def contracts_site(self, needle: str) -> tuple[int, int]:
        """Line of the first contracts.toml line containing ``needle`` —
        an anchor for contract-side findings."""
        try:
            with open(self.contracts_path, encoding="utf-8") as f:
                for i, line in enumerate(f, start=1):
                    if needle in line:
                        return i, line.index(needle)
        except OSError:
            pass
        return 1, 0


def _f(rule, site: ex.Site, message: str, symbol: str) -> Finding:
    return Finding(rule, site.path, site.line, site.col, message,
                   symbol=symbol)


# -- WIR001 ------------------------------------------------------------------

def check_surfaces(ctx: CheckContext) -> tuple[list[Finding],
                                               list[Finding]]:
    findings, warns = [], []
    for spec in ctx.contracts.surfaces:
        f, w = _check_surface(ctx, spec)
        findings += f
        warns += w
    return findings, warns


def _check_surface(ctx: CheckContext, spec: SurfaceSpec):
    findings: list[Finding] = []
    warns: list[Finding] = []
    produced: dict[str, ex.Site] = {}
    produced_fams: dict[str, ex.Site] = {}
    reads: list[ex.KeyUse] = []

    for path, qual, var in spec.producers:
        idx = ctx.index(path)
        sk = ex.extract_scope_keys(idx, qual, var, producer=True) \
            if idx else None
        if sk is None:
            findings.append(Finding(
                "WIR001", ctx.contracts_rel,
                *ctx.contracts_site(qual),
                f"surface '{spec.name}': producer scope "
                f"{path}::{qual} not found — contracts.toml is stale",
                symbol=f"{spec.name}.producer.{qual}"))
            continue
        for use in sk.writes:
            (produced_fams if use.family else produced).setdefault(
                use.key, use.site)
    for path, qual, var in spec.consumers:
        idx = ctx.index(path)
        sk = ex.extract_scope_keys(idx, qual, var, producer=False) \
            if idx else None
        if sk is None:
            findings.append(Finding(
                "WIR001", ctx.contracts_rel,
                *ctx.contracts_site(qual),
                f"surface '{spec.name}': consumer scope "
                f"{path}::{qual} not found — contracts.toml is stale",
                symbol=f"{spec.name}.consumer.{qual}"))
            continue
        reads.extend(sk.reads)
    for path, const in spec.consumer_lists:
        idx = ctx.index(path)
        keys = ex.extract_const_list(idx, const) if idx else []
        if not keys:
            findings.append(Finding(
                "WIR001", ctx.contracts_rel,
                *ctx.contracts_site(const),
                f"surface '{spec.name}': consumer list {path}::{const} "
                "not found or empty — contracts.toml is stale",
                symbol=f"{spec.name}.consumer_list.{const}"))
            continue
        line = idx.consts_lineno.get(const, 1)
        for key in keys:
            reads.append(ex.KeyUse(key, ex.Site(path, line, 0, const)))

    declared = set(spec.fields) | set(spec.synthetic)
    produced_all = set(produced) | set(spec.synthetic)

    def _produced(key: str) -> bool:
        return key in produced_all or \
            any(key.startswith(p) for p in produced_fams)

    def _declared(key: str) -> bool:
        return key in declared or \
            any(key.startswith(p) for p in spec.families)

    # phantom consumer: a read no producer satisfies
    for use in reads:
        if use.family:
            ok = use.key in produced_fams or \
                any(k.startswith(use.key) for k in produced_all)
            if not ok:
                findings.append(_f(
                    "WIR001", use.site,
                    f"surface '{spec.name}': consumer reads the "
                    f"'{use.key}*' family but no producer writes it — "
                    "the reads silently see nothing", use.key))
        elif not _produced(use.key):
            findings.append(_f(
                "WIR001", use.site,
                f"surface '{spec.name}': consumer reads '{use.key}' but "
                "no producer writes it — the read silently defaults",
                use.key))

    # contract rot: declared field nothing produces
    for key in spec.fields:
        if not _produced(key):
            findings.append(Finding(
                "WIR001", ctx.contracts_rel, *ctx.contracts_site(key),
                f"surface '{spec.name}': contract declares '{key}' but "
                "no producer writes it — fix the producer or prune the "
                "contract", symbol=f"{spec.name}.{key}"))

    # undeclared production: a write the contract does not know
    for key, site in produced.items():
        if not _declared(key):
            findings.append(_f(
                "WIR001", site,
                f"surface '{spec.name}': producer writes '{key}' but "
                "contracts.toml does not declare it — add it to the "
                "surface field list (and a consumer, or dead_ok)", key))
    for fam, site in produced_fams.items():
        if fam not in spec.families:
            findings.append(_f(
                "WIR001", site,
                f"surface '{spec.name}': producer writes the '{fam}*' "
                "family but contracts.toml does not declare it in "
                "families", fam))

    # dead telemetry (warn tier): produced, declared, nobody reads it
    read_exact = {u.key for u in reads if not u.family}
    read_fams = {u.key for u in reads if u.family}
    consumed_extra = set(spec.manual_consumed)

    def _consumed(key: str) -> bool:
        return key in read_exact or key in consumed_extra or \
            any(key.startswith(p) for p in read_fams)

    for key in sorted(produced_all):
        if _declared(key) and not _consumed(key) \
                and key not in spec.dead_ok:
            site = produced.get(key)
            if site is None:
                line, col = ctx.contracts_site(key)
                site = ex.Site(ctx.contracts_rel, line, col, spec.name)
            warns.append(_f(
                "WIR001", site,
                f"surface '{spec.name}': '{key}' is produced but no "
                "declared consumer reads it — dead telemetry (add a "
                "consumer, or a dead_ok entry with a reason)", key))
    return findings, warns


# -- WIR002 ------------------------------------------------------------------

def check_metrics(ctx: CheckContext) -> tuple[list[Finding],
                                              list[Finding]]:
    findings, warns = [], []
    c = ctx.contracts
    emitted = {u.name for u in ctx.metric_emits if not u.family}
    emitted_fams = {u.name for u in ctx.metric_emits if u.family} \
        | set(c.metric_dynamic_prefixes)
    removed = {u.name for u in ctx.metric_removes if not u.family}
    removed_fams = {u.name for u in ctx.metric_removes if u.family}

    def _emitted(name: str) -> bool:
        return name in emitted or \
            any(name.startswith(p) for p in emitted_fams)

    # asserted-but-never-emitted: a test/CLI/guard naming a ghost series
    seen_assert: set[tuple] = set()
    for use in ctx.metric_asserts:
        if _emitted(use.name) or (use.name, use.site.path) in seen_assert:
            continue
        seen_assert.add((use.name, use.site.path))
        findings.append(_f(
            "WIR002", use.site,
            f"'{use.name}' is asserted here but nothing in tpu9/ emits "
            "it — the assertion tests a ghost series", use.name))

    # per-entity gauges need remove_gauge coverage (PR 14 class)
    entity = set(c.metric_entity_labels)
    seen_gauge: set[str] = set()
    for use in ctx.metric_emits:
        if use.method != "set_gauge" or use.name in seen_gauge:
            continue
        if not (entity & set(use.label_keys)):
            continue
        seen_gauge.add(use.name)
        covered = use.name in removed or \
            any(use.name.startswith(p) for p in removed_fams) or \
            (use.family and use.name in removed_fams)
        if not covered and use.name not in c.metric_remove_ok:
            label = sorted(entity & set(use.label_keys))[0]
            findings.append(_f(
                "WIR002", use.site,
                f"per-{label} gauge '{use.name}{'*' if use.family else ''}'"
                " has no remove_gauge coverage — dead entities keep their "
                "last value forever and the series set grows without "
                "bound under churn", use.name))

    # emitted-but-never-asserted (warn tier)
    asserted = {u.name for u in ctx.metric_asserts}
    for use in ctx.metric_emits:
        if use.family or use.name in asserted or \
                use.name in c.metric_assert_ok:
            continue
        if any(use.name.startswith(p) and p in asserted
               for p in emitted_fams):
            continue
        asserted.add(use.name)     # one warn per name
        warns.append(_f(
            "WIR002", use.site,
            f"'{use.name}' is emitted but never asserted in tests/CLI — "
            "unwatched telemetry (assert it somewhere, or add an "
            "assert_ok entry with a reason)", use.name))
    return findings, warns


# -- KEY001 ------------------------------------------------------------------

def check_store_keys(ctx: CheckContext) -> tuple[list[Finding],
                                                 list[Finding]]:
    findings: list[Finding] = []
    specs = ctx.contracts.keys

    def _spec_for(key: str):
        best = None
        for s in specs:
            pat = s.pattern
            if pat.endswith("*"):
                if key.startswith(pat[:-1]) or key == pat[:-1].rstrip(":"):
                    if best is None or len(pat) > len(best.pattern):
                        best = s
            elif key == pat:
                return s
        return best

    seen_undeclared: set[tuple] = set()
    for op in ctx.store_ops:
        spec = _spec_for(op.key)
        if spec is None:
            k = (op.key, op.site.path)
            if k not in seen_undeclared:
                seen_undeclared.add(k)
                findings.append(_f(
                    "KEY001", op.site,
                    f"store key '{op.key}' matches no namespace declared "
                    "in contracts.toml — declare its writer plane, TTL "
                    "discipline and atomicity", op.key))
            continue
        if op.op in ex.STORE_WRITE_OPS:
            if spec.writers and not any(
                    op.site.path.startswith(w) for w in spec.writers):
                findings.append(_f(
                    "KEY001", op.site,
                    f"'{op.op}' on '{op.key}' from {op.site.path} — "
                    f"namespace '{spec.name}' declares writers "
                    f"{spec.writers}; cross-plane writes race the owner",
                    op.key))
            if spec.atomic and op.op in ("set", "hset", "hmset"):
                findings.append(_f(
                    "KEY001", op.site,
                    f"plain '{op.op}' on atomic namespace '{spec.name}' "
                    f"('{op.key}') — multi-writer keys must use the "
                    "atomic list/CAS ops (rpush/ltrim/cas); read-modify-"
                    "write erases concurrent writes", op.key))
            if spec.ttl == "required" and not op.has_ttl and \
                    op.op in ("set", "hset", "hmset") and \
                    not _expire_in_scope(ctx, op):
                findings.append(_f(
                    "KEY001", op.site,
                    f"TTL-less '{op.op}' on '{op.key}' — namespace "
                    f"'{spec.name}' requires TTL discipline (pass ttl= "
                    "or expire() in the same scope); an unreaped key "
                    "leaks state forever", op.key))
    return findings, []


def _expire_in_scope(ctx: CheckContext, op: ex.StoreOp) -> bool:
    prefix = op.key.split("*")[0]
    return any(o.op == "expire" and o.site.path == op.site.path
               and o.site.symbol == op.site.symbol
               and o.key.split("*")[0] == prefix
               for o in ctx.store_ops)


# -- ENV001 ------------------------------------------------------------------

# reads here are the *point* of the rule — the accessor every other
# plane is told to route through — so they are implicitly declared
ENV_HOME = "tpu9/config.py"


def check_env(ctx: CheckContext) -> tuple[list[Finding], list[Finding]]:
    findings: list[Finding] = []
    declared = ctx.contracts.env
    by_var: dict[str, list[ex.EnvRead]] = {}
    for r in ctx.env_reads:
        by_var.setdefault(r.var, []).append(r)
    for var, uses in sorted(by_var.items()):
        readers = declared.get(var)
        if readers is None:
            for use in uses:
                if use.site.path == ENV_HOME:
                    continue    # the canonical accessor home needs no entry
                findings.append(_f(
                    "ENV001", use.site,
                    f"'{var}' is read here but not declared in "
                    "contracts.toml [env] — route it through "
                    "tpu9/config.py or declare its reader", var))
            continue
        for use in uses:
            if use.site.path == ENV_HOME:
                continue
            if not any(use.site.path.startswith(r) for r in readers):
                findings.append(_f(
                    "ENV001", use.site,
                    f"'{var}' read outside its declared readers "
                    f"{readers} — a second reader grows a second "
                    "default; route through tpu9/config.py", var))
        defaults = {u.default for u in uses}
        if len(defaults) > 1 and var not in ctx.contracts.env_divergent_ok:
            site = sorted(uses, key=lambda u: (u.site.path,
                                               u.site.line))[-1].site
            findings.append(_f(
                "ENV001", site,
                f"'{var}' has divergent inline defaults across its "
                f"readers: {sorted(defaults)} — the effective value "
                "depends on which plane asks; hoist one default into "
                "tpu9/config.py", var))
    return findings, []


# -- RPC001 ------------------------------------------------------------------

def check_rpc(ctx: CheckContext) -> tuple[list[Finding], list[Finding]]:
    findings: list[Finding] = []
    c = ctx.contracts
    seen: set[str] = set()
    for reg in ctx.routes_registered:
        if reg.pattern in seen:
            continue
        seen.add(reg.pattern)
        called = any(ex.route_match(reg.pattern, call.pattern)
                     for call in ctx.route_calls)
        if not called and reg.pattern not in c.rpc_external_ok:
            findings.append(_f(
                "RPC001", reg.site,
                f"route '{reg.pattern}' is registered but nothing in the "
                "repo calls it — dead handler (or declare it external_ok "
                "with a reason)", reg.pattern))
    seen_calls: set[tuple] = set()
    for call in ctx.route_calls:
        key = (call.pattern, call.site.path)
        if key in seen_calls:
            continue
        seen_calls.add(key)
        handled = any(ex.route_match(reg.pattern, call.pattern)
                      for reg in ctx.routes_registered)
        if not handled and call.pattern not in c.rpc_call_only_ok:
            findings.append(_f(
                "RPC001", call.site,
                f"'{call.pattern}' is called here but no handler "
                "registers it — the call can only 404", call.pattern))
    return findings, []


ALL_CHECKS = {
    "WIR001": check_surfaces,
    "WIR002": check_metrics,
    "KEY001": check_store_keys,
    "ENV001": check_env,
    "RPC001": check_rpc,
}
