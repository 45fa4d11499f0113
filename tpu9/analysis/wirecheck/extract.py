"""AST extraction for wirecheck (ISSUE 18).

Everything here is *positive-evidence* extraction: a key/name/route is
collected only when it appears in a syntactic position that ties it to a
wire surface (a read off a declared dict variable, the name argument of a
metrics call, the key argument of a state-store op, …). Bare string
literals never count on their own — that is what keeps the checker's
false-positive rate near zero on a repo that is full of strings.

The extractors are deliberately scope-driven: ``contracts.toml`` names the
producer and consumer scopes as ``path::qualname::var`` and extraction
happens only inside those scopes, against that variable. A consumer
function that also touches three other payload dicts contributes nothing
from them.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

KEY_RE = re.compile(r"^[a-z][a-z0-9_]+$")
METRIC_RE = re.compile(r"^tpu9_[a-z0-9_]+$")
METRIC_METHODS = ("inc", "observe", "set_gauge", "remove_gauge")
# ops that CREATE/overwrite state (writer-plane checked); pops/trims/
# deletes/expires are consumer-side lifecycle and stay exempt
STORE_WRITE_OPS = ("set", "hset", "hmset", "rpush", "lpush", "incr",
                   "hincr", "cas")
STORE_READ_OPS = ("get", "hget", "hgetall", "lrange", "llen", "keys",
                  "exists", "blpop", "lpop")
STORE_LIFECYCLE_OPS = ("delete", "expire", "ltrim", "lrem", "hdel",
                       "acquire_lock", "release_lock")
STORE_OPS = STORE_WRITE_OPS + STORE_READ_OPS + STORE_LIFECYCLE_OPS
ROUTE_REGISTER = ("add_get", "add_post", "add_put", "add_delete",
                  "add_route")
ROUTE_PREFIXES = ("/rpc/", "/api/v1/")


@dataclass
class Site:
    """One extracted occurrence, enough to mint a Finding."""
    path: str           # repo-relative, posix
    line: int
    col: int
    symbol: str         # enclosing qualname
    detail: str = ""


@dataclass
class KeyUse:
    key: str
    site: Site
    family: bool = False      # key is a prefix (startswith / f-string)


@dataclass
class StoreOp:
    key: str                  # normalized: placeholders -> '*'
    op: str
    site: Site
    has_ttl: bool = False


@dataclass
class EnvRead:
    var: str
    default: str              # unparsed default expr, '<required>' if none
    site: Site


@dataclass
class MetricUse:
    name: str
    method: str               # inc / observe / set_gauge / remove_gauge
    site: Site
    family: bool = False      # name is an f-string prefix
    label_keys: tuple = ()


@dataclass
class RouteUse:
    pattern: str              # normalized: {param} / f-holes -> '*'
    site: Site


@dataclass
class ModuleIndex:
    """Per-file parse products reused by every rule."""
    path: str
    tree: ast.AST
    source: str
    consts: dict = field(default_factory=dict)   # NAME -> str|tuple struct
    consts_lineno: dict = field(default_factory=dict)
    scopes: dict = field(default_factory=dict)   # qualname -> ast node


# -- module indexing ---------------------------------------------------------

def _const_struct(node):
    """Literal str, or (possibly nested) tuple/list of literal strs."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            sub = _const_struct(elt)
            if sub is None:
                return None
            out.append(sub)
        return tuple(out)
    return None


class _Indexer(ast.NodeVisitor):
    def __init__(self, idx: ModuleIndex):
        self.idx = idx
        self.stack: list[str] = []

    def _qual(self, name: str) -> str:
        return ".".join(self.stack + [name])

    def visit_ClassDef(self, node):
        self.idx.scopes[self._qual(node.name)] = node
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _func(self, node):
        self.idx.scopes[self._qual(node.name)] = node
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _func
    visit_AsyncFunctionDef = _func

    def visit_Assign(self, node):
        if not self.stack:                      # module level only
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    struct = _const_struct(node.value)
                    if struct is not None:
                        self.idx.consts[tgt.id] = struct
                        self.idx.consts_lineno[tgt.id] = node.lineno
        self.generic_visit(node)


def index_module(repo_root: str, rel_path: str) -> "ModuleIndex | None":
    full = os.path.join(repo_root, rel_path)
    try:
        with open(full, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=rel_path)
    except (OSError, SyntaxError):
        return None
    idx = ModuleIndex(path=rel_path.replace(os.sep, "/"), tree=tree,
                      source=source)
    _Indexer(idx).visit(tree)
    return idx


def enclosing_symbols(tree: ast.AST) -> dict:
    """id(node) -> qualname of the enclosing function/class."""
    out: dict = {}

    def walk(node, qual):
        for child in ast.iter_child_nodes(node):
            q = qual
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                q = f"{qual}.{child.name}" if qual != "<module>" \
                    else child.name
            out[id(child)] = q
            walk(child, q)
    out[id(tree)] = "<module>"
    walk(tree, "<module>")
    return out


# -- scoped dict-key extraction (WIR001) -------------------------------------

def _matches_var(node, var: str) -> bool:
    if "." in var:                              # e.g. "self._stats"
        head, attr = var.rsplit(".", 1)
        return (isinstance(node, ast.Attribute) and node.attr == attr
                and isinstance(node.value, ast.Name)
                and node.value.id == head)
    return isinstance(node, ast.Name) and node.id == var


def _lit_str(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _joined_prefix(node):
    """f-string with a leading literal part -> that prefix, else None."""
    if isinstance(node, ast.JoinedStr) and node.values:
        head = _lit_str(node.values[0])
        if head:
            return head
    return None


class _ScopeKeys:
    """Reads and writes of one dict variable inside one scope."""

    def __init__(self, idx: ModuleIndex, scope_node, scope_qual: str,
                 var: str):
        self.idx = idx
        self.node = scope_node
        self.qual = scope_qual
        self.var = var
        self.reads: list[KeyUse] = []
        self.writes: list[KeyUse] = []
        self._aliases: set[str] = set()          # loop vars over the dict
        self._accessors: set[str] = set()        # nested closures over var

    def _site(self, node, detail="") -> Site:
        return Site(self.idx.path, node.lineno, node.col_offset,
                    self.qual, detail)

    def _is_var(self, node) -> bool:
        return _matches_var(node, self.var)

    def run(self):
        self._find_aliases_and_accessors()
        for node in ast.walk(self.node):
            self._collect(node)
        return self

    def _find_aliases_and_accessors(self):
        for node in ast.walk(self.node):
            # for k in var / var.keys() / var.items()  -> k aliases a key
            if isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                if isinstance(it, ast.Call) and \
                        isinstance(it.func, ast.Attribute) and \
                        it.func.attr in ("keys", "items") and \
                        self._is_var(it.func.value):
                    tgt = node.target
                    if it.func.attr == "items" and \
                            isinstance(tgt, ast.Tuple) and tgt.elts:
                        tgt = tgt.elts[0]
                    if isinstance(tgt, ast.Name):
                        self._aliases.add(tgt.id)
                elif self._is_var(it):
                    if isinstance(node.target, ast.Name):
                        self._aliases.add(node.target.id)
            # nested closure reading var -> literal call args are reads
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not self.node:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Attribute) and \
                            sub.attr in ("get", "pop") and \
                            self._is_var(sub.value):
                        self._accessors.add(node.name)
                        break
                    if isinstance(sub, ast.Subscript) and \
                            self._is_var(sub.value):
                        self._accessors.add(node.name)
                        break

    def _read(self, key, node, family=False, detail=""):
        if family or KEY_RE.match(key):
            self.reads.append(KeyUse(key, self._site(node, detail), family))

    def _write(self, key, node, family=False, detail=""):
        if family or KEY_RE.match(key):
            self.writes.append(KeyUse(key, self._site(node, detail),
                                      family))

    def _collect(self, node):
        # var["k"] loads/stores, var[f"pfx{..}"] family stores
        if isinstance(node, ast.Subscript) and self._is_var(node.value):
            key = _lit_str(node.slice)
            prefix = _joined_prefix(node.slice)
            if isinstance(node.ctx, ast.Store):
                if key is not None:
                    self._write(key, node)
                elif prefix is not None:
                    self._write(prefix, node, family=True)
            elif isinstance(node.ctx, ast.Load) and key is not None:
                self._read(key, node)
            return
        # "k" in var
        if isinstance(node, ast.Compare) and node.comparators and \
                len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                self._is_var(node.comparators[0]):
            key = _lit_str(node.left)
            if key is not None:
                self._read(key, node)
            return
        if isinstance(node, ast.Call):
            self._collect_call(node)
            return
        # var = {...} / augmented forms handled via Subscript above
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if self._is_var(tgt) and isinstance(node.value, ast.Dict):
                    for k in node.value.keys:
                        key = _lit_str(k)
                        if key is not None:
                            self._write(key, k)
        # k.startswith("pfx") where k loops over the dict -> family read
        # (producer scopes translate these into family writes in
        #  finish() when the scope also stores dynamic keys)

    def _collect_call(self, node: ast.Call):
        func = node.func
        # var.get("k") / var.pop / var.setdefault
        if isinstance(func, ast.Attribute) and self._is_var(func.value):
            if func.attr in ("get", "pop") and node.args:
                key = _lit_str(node.args[0])
                if key is not None:
                    self._read(key, node)
            elif func.attr == "setdefault" and node.args:
                key = _lit_str(node.args[0])
                if key is not None:
                    self._write(key, node)
            elif func.attr == "update" and node.args and \
                    isinstance(node.args[0], ast.Dict):
                for k in node.args[0].keys:
                    key = _lit_str(k)
                    if key is not None:
                        self._write(key, k)
            return
        # alias.startswith("pfx") -> family use
        if isinstance(func, ast.Attribute) and \
                func.attr == "startswith" and \
                isinstance(func.value, ast.Name) and \
                func.value.id in self._aliases and node.args:
            arg = node.args[0]
            prefixes = []
            if _lit_str(arg) is not None:
                prefixes = [_lit_str(arg)]
            elif isinstance(arg, (ast.Tuple, ast.List)):
                prefixes = [p for p in map(_lit_str, arg.elts) if p]
            for p in prefixes:
                self._read(p, node, family=True)
            return
        # accessor closure: _f("k")
        if isinstance(func, ast.Name) and func.id in self._accessors \
                and node.args:
            key = _lit_str(node.args[0])
            if key is not None:
                self._read(key, node, detail=f"via {func.id}()")
            return
        # helper taking (var, "k") in any positions: _num(stats, "k")
        if isinstance(func, (ast.Name, ast.Attribute)):
            has_var = any(self._is_var(a) for a in node.args)
            if has_var:
                for a in node.args:
                    key = _lit_str(a)
                    if key is not None and KEY_RE.match(key):
                        self._read(key, a)

    def finish_consumer(self):
        """Consumer-only post-pass: ``for k in ("a", "b"): ... var[k]``
        (or ``k in var`` / ``var.get(k)``) reads every tuple element."""
        for node in ast.walk(self.node):
            if not isinstance(node, ast.For) or \
                    not isinstance(node.iter, (ast.Tuple, ast.List)):
                continue
            tgt = node.target
            if not isinstance(tgt, ast.Name):
                continue
            loop_var = tgt.id

            def _keyed_by_loop(n):
                if isinstance(n, ast.Subscript) and self._is_var(n.value) \
                        and isinstance(n.slice, ast.Name) \
                        and n.slice.id == loop_var:
                    return True
                if isinstance(n, ast.Compare) and \
                        isinstance(n.left, ast.Name) and \
                        n.left.id == loop_var and \
                        any(isinstance(op, (ast.In, ast.NotIn))
                            for op in n.ops) and \
                        n.comparators and self._is_var(n.comparators[0]):
                    return True
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr in ("get", "pop") and \
                        self._is_var(n.func.value) and n.args and \
                        isinstance(n.args[0], ast.Name) and \
                        n.args[0].id == loop_var:
                    return True
                return False

            if not any(_keyed_by_loop(n) for n in ast.walk(node)):
                continue
            for elt in node.iter.elts:
                key = _lit_str(elt)
                if key is not None and KEY_RE.match(key):
                    self._read(key, elt, detail="tuple loop")
        return self

    def finish_producer(self):
        """Producer-only post-pass: forwarded literal tuples and
        startswith-filtered copy loops become writes."""
        for node in ast.walk(self.node):
            if not isinstance(node, ast.For):
                continue
            # loop target name(s): `for k in ...` or `for k, v in ...`
            tgt = node.target
            names = [tgt.id] if isinstance(tgt, ast.Name) else \
                [t.id for t in tgt.elts if isinstance(t, ast.Name)] \
                if isinstance(tgt, ast.Tuple) else []
            if not names:
                continue
            loop_var = names[0]
            stores = any(
                isinstance(n, ast.Subscript) and self._is_var(n.value)
                and isinstance(n.ctx, ast.Store)
                and isinstance(n.slice, ast.Name)
                and n.slice.id == loop_var
                for n in ast.walk(node))
            if not stores:
                continue
            # for k in ("a", "b", ...): ... var[k] = ...
            if isinstance(node.iter, (ast.Tuple, ast.List)):
                for elt in node.iter.elts:
                    key = _lit_str(elt)
                    if key is not None:
                        self._write(key, elt, detail="forwarded tuple")
            # for k, v in <src>.items(): if k.startswith("pfx"): var[k]=v
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "startswith" and \
                        isinstance(sub.func.value, ast.Name) and \
                        sub.func.value.id == loop_var and sub.args:
                    arg = sub.args[0]
                    prefixes = [_lit_str(arg)] \
                        if _lit_str(arg) is not None else \
                        [p for p in map(_lit_str, arg.elts) if p] \
                        if isinstance(arg, (ast.Tuple, ast.List)) else []
                    for p in prefixes:
                        self._write(p, sub, family=True,
                                    detail="forwarded family")
        return self


def extract_scope_keys(idx: ModuleIndex, qualname: str, var: str,
                       producer: bool) -> "_ScopeKeys | None":
    node = idx.scopes.get(qualname)
    if node is None:
        return None
    sk = _ScopeKeys(idx, node, qualname, var).run()
    if producer:
        sk.finish_producer()
    else:
        sk.finish_consumer()
    return sk


def extract_const_list(idx: ModuleIndex, name: str) -> list[str]:
    """Flatten a module-level str tuple/list constant (nested pairs ok),
    keeping only dict-key-looking strings (metric names filtered out)."""
    struct = idx.consts.get(name)
    out: list[str] = []

    def flat(s):
        if isinstance(s, str):
            if KEY_RE.match(s) and not s.startswith("tpu9_"):
                out.append(s)
        elif isinstance(s, tuple):
            for e in s:
                flat(e)
    if struct is not None:
        flat(struct)
    return out


# -- metrics (WIR002) --------------------------------------------------------

def _resolve_metric_names(arg, enclosing_fn, idx: ModuleIndex):
    """First arg of a metrics call -> [(name, family?)]; resolves loop
    vars iterating module-level tuples (the health.py gauge-family
    pattern, incl. ``for gauge, key in PAIRS``)."""
    lit = _lit_str(arg)
    if lit is not None:
        return [(lit, False)]
    prefix = _joined_prefix(arg)
    if prefix is not None:
        return [(prefix, True)]
    if isinstance(arg, ast.Name) and enclosing_fn is not None:
        names = []
        for node in ast.walk(enclosing_fn):
            if not isinstance(node, (ast.For, ast.comprehension)):
                continue
            it = node.iter
            const = idx.consts.get(it.id) if isinstance(it, ast.Name) \
                else _const_struct(it)
            if const is None:
                continue
            tgt = node.target
            if isinstance(tgt, ast.Name) and tgt.id == arg.id:
                names += [(e, False) for e in const
                          if isinstance(e, str)]
            elif isinstance(tgt, ast.Tuple):
                for pos, t in enumerate(tgt.elts):
                    if isinstance(t, ast.Name) and t.id == arg.id:
                        names += [(e[pos], False) for e in const
                                  if isinstance(e, tuple)
                                  and len(e) > pos
                                  and isinstance(e[pos], str)]
        return [(n, fam) for n, fam in names if n.startswith("tpu9_")]
    return []


def _label_keys(call: ast.Call, enclosing_fn) -> tuple:
    labels = None
    for kw in call.keywords:
        if kw.arg == "labels":
            labels = kw.value
    if labels is None and len(call.args) >= 3:
        labels = call.args[2]
    if isinstance(labels, ast.Name) and enclosing_fn is not None:
        for node in ast.walk(enclosing_fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Dict) and \
                    any(isinstance(t, ast.Name) and t.id == labels.id
                        for t in node.targets):
                labels = node.value
    if isinstance(labels, ast.Dict):
        return tuple(k for k in map(_lit_str, labels.keys) if k)
    return ()


def extract_metrics(idx: ModuleIndex) -> list[MetricUse]:
    symbols = enclosing_symbols(idx.tree)
    # map each call to its enclosing function node for name resolution
    fn_of: dict[int, ast.AST] = {}

    def assign_fns(node, fn):
        for child in ast.iter_child_nodes(node):
            f = child if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else fn
            fn_of[id(child)] = f
            assign_fns(child, f)
    assign_fns(idx.tree, None)

    out: list[MetricUse] = []
    for node in ast.walk(idx.tree):
        emits = _metric_call(node)
        if emits is None:
            continue
        method, name_arg = emits
        fn = fn_of.get(id(node))
        for name, family in _resolve_metric_names(name_arg, fn, idx):
            if not family and not METRIC_RE.match(name):
                continue
            out.append(MetricUse(
                name, method,
                Site(idx.path, node.lineno, node.col_offset,
                     symbols.get(id(node), "<module>")),
                family=family,
                label_keys=_label_keys(node, fn)))
    return out


def _metric_call(node):
    """``(method, the argument naming the series)`` of a call that emits a
    metric, or None: ``<...metric...>.<METRIC_METHODS>(name, ...)``, and
    ``<tracer>.record_interval(span, registry, summary, ...)`` — one
    interval told as a span and as an observation of ``summary``
    (``tpu9/observability/trace.py``)."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)):
        return None
    if node.func.attr == "record_interval" and len(node.args) >= 3:
        return "observe", node.args[2]
    if node.func.attr not in METRIC_METHODS or not node.args:
        return None
    recv = node.func.value
    recv_name = recv.id if isinstance(recv, ast.Name) else \
        recv.attr if isinstance(recv, ast.Attribute) else ""
    return (node.func.attr, node.args[0]) if "metric" in recv_name else None


def extract_metric_literals(idx: ModuleIndex) -> list[MetricUse]:
    """Every ``tpu9_*`` string literal in a file (the *asserted* side:
    tests, CLI renderers, docs-in-code). Emission calls are collected
    separately — the checker subtracts them."""
    symbols = enclosing_symbols(idx.tree)
    out = []
    for node in ast.walk(idx.tree):
        lit = _lit_str(node) if isinstance(node, ast.Constant) else None
        if lit and METRIC_RE.match(lit):
            out.append(MetricUse(
                lit, "literal",
                Site(idx.path, node.lineno, node.col_offset,
                     symbols.get(id(node), "<module>"))))
    return out


# -- store keys (KEY001) -----------------------------------------------------

_PLACEHOLDER = re.compile(r"\{[^}]*\}|%s|%d")


def _normalize_key(raw: str) -> str:
    return _PLACEHOLDER.sub("*", raw)


def _resolve_key_arg(arg, idx: ModuleIndex):
    lit = _lit_str(arg)
    if lit is not None:
        return _normalize_key(lit)
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            p = _lit_str(v)
            parts.append(p if p is not None else "*")
        return _normalize_key("".join(parts))
    if isinstance(arg, ast.Name):
        const = idx.consts.get(arg.id)
        if isinstance(const, str):
            return _normalize_key(const)
        return None
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        left = _resolve_key_arg(arg.left, idx)
        if left is not None:
            right = _resolve_key_arg(arg.right, idx)
            return left + (right if right is not None else "*")
        return None
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Mod):
        left = _lit_str(arg.left)
        if left is not None:
            return _normalize_key(left)
        return None
    if isinstance(arg, ast.Call) and \
            isinstance(arg.func, ast.Attribute) and \
            arg.func.attr == "format":
        return _resolve_key_arg(arg.func.value, idx)
    return None


def extract_store_ops(idx: ModuleIndex) -> list[StoreOp]:
    symbols = enclosing_symbols(idx.tree)
    out = []
    for node in ast.walk(idx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in STORE_OPS
                and node.args):
            continue
        recv = node.func.value
        recv_name = recv.id if isinstance(recv, ast.Name) else \
            recv.attr if isinstance(recv, ast.Attribute) else ""
        if "store" not in recv_name:
            continue
        key = _resolve_key_arg(node.args[0], idx)
        if key is None or (":" not in key and "*" not in key):
            continue
        has_ttl = any(kw.arg == "ttl" and
                      not (isinstance(kw.value, ast.Constant)
                           and kw.value.value is None)
                      for kw in node.keywords)
        out.append(StoreOp(key, node.func.attr,
                           Site(idx.path, node.lineno, node.col_offset,
                                symbols.get(id(node), "<module>")),
                           has_ttl=has_ttl))
    return out


# -- env reads (ENV001) ------------------------------------------------------

def _is_environ(node) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr == "environ"
    return isinstance(node, ast.Name) and node.id == "environ"


def extract_env_reads(idx: ModuleIndex) -> list[EnvRead]:
    symbols = enclosing_symbols(idx.tree)
    # `env.get(...) or X` — the effective default is X, so capture the
    # BoolOp tail for divergence comparison
    or_tail: dict[int, str] = {}
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.Or) \
                and len(node.values) >= 2:
            try:
                or_tail[id(node.values[0])] = ast.unparse(node.values[1])
            except Exception:
                pass
    out = []
    for node in ast.walk(idx.tree):
        var = default = None
        if isinstance(node, ast.Call) and node.args:
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "get" \
                    and _is_environ(func.value):
                var = _lit_str(node.args[0])
                default = ast.unparse(node.args[1]) \
                    if len(node.args) > 1 else "<required>"
            elif isinstance(func, ast.Attribute) and \
                    func.attr == "getenv" or \
                    isinstance(func, ast.Name) and func.id == "getenv":
                var = _lit_str(node.args[0])
                default = ast.unparse(node.args[1]) \
                    if len(node.args) > 1 else "<required>"
        elif isinstance(node, ast.Subscript) and \
                _is_environ(node.value) and \
                isinstance(node.ctx, ast.Load):
            var = _lit_str(node.slice)
            default = "<required>"
        if var is None or not var.startswith("TPU9_"):
            continue
        tail = or_tail.get(id(node))
        if tail is not None:
            default = f"{default} or {tail}"
        out.append(EnvRead(var, default,
                           Site(idx.path, node.lineno, node.col_offset,
                                symbols.get(id(node), "<module>"))))
    return out


# -- rpc routes (RPC001) -----------------------------------------------------

def _route_pattern(raw: str) -> str:
    return _PLACEHOLDER.sub("*", raw.split("?")[0])


def extract_routes(idx: ModuleIndex) -> tuple[list[RouteUse],
                                              list[RouteUse]]:
    """(registered, called). Call-site literals are any string containing
    a route prefix outside registration calls and docstrings."""
    symbols = enclosing_symbols(idx.tree)
    registered: list[RouteUse] = []
    called: list[RouteUse] = []
    skip_ids: set[int] = set()

    # docstrings: standalone string expressions
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Expr) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, str):
            skip_ids.add(id(node.value))

    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in ROUTE_REGISTER:
            arg_i = 1 if node.func.attr == "add_route" else 0
            if len(node.args) > arg_i:
                path = _lit_str(node.args[arg_i])
                if path and path.startswith(ROUTE_PREFIXES):
                    registered.append(RouteUse(
                        _route_pattern(path),
                        Site(idx.path, node.lineno, node.col_offset,
                             symbols.get(id(node), "<module>"))))
                    skip_ids.add(id(node.args[arg_i]))

    for node in ast.walk(idx.tree):
        text = None
        if isinstance(node, ast.Constant) and id(node) not in skip_ids:
            text = _lit_str(node)
        elif isinstance(node, ast.JoinedStr):
            parts = []
            for v in node.values:
                p = _lit_str(v)
                parts.append(p if p is not None else "*")
            text = "".join(parts)
        if not text:
            continue
        for prefix in ROUTE_PREFIXES:
            pos = text.find(prefix)
            if pos >= 0:
                called.append(RouteUse(
                    _route_pattern(text[pos:]),
                    Site(idx.path, node.lineno, node.col_offset,
                         symbols.get(id(node), "<module>"))))
                break
    return registered, called


def route_match(reg: str, call: str) -> bool:
    """Segment-wise match where '*' wildcards one segment on either side.

    Asymmetric on the *call* side: string-concat builds
    (``"/rpc/pod/" + name`` → pattern ``/rpc/pod/``) and f-string tails
    (``f"/rpc/pod/{name}"`` → ``/rpc/pod/*``) are prefixes — they match
    any registered route that shares the leading segments, even a longer
    one.  Registered patterns are always full paths and never
    prefix-match."""
    sr = reg.rstrip("/").split("/")
    sc = call.rstrip("/").split("/")
    seg_ok = lambda x, y: x == y or x == "*" or y == "*"
    if sc and sc[-1].endswith("*"):
        # f-string tail: the last call segment is open-ended.  ``machine*``
        # (query string in the variable) needs the stem to prefix the
        # registered segment; ``**`` (path tail in the variable) matches
        # any suffix.
        if len(sr) < len(sc):
            return False
        if not all(seg_ok(x, y) for x, y in zip(sr[:len(sc) - 1], sc[:-1])):
            return False
        stem = sc[-1].rstrip("*")
        last = sr[len(sc) - 1]
        return last == "*" or last.startswith(stem)
    if call.endswith("/"):
        # string-concat build: the call literal stops at a separator
        if len(sc) > len(sr):
            return False
        sr = sr[:len(sc)]
    elif len(sr) != len(sc):
        return False
    return all(seg_ok(x, y) for x, y in zip(sr, sc))
