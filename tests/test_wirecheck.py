"""wirecheck (ISSUE 18): per-rule fixtures on a mini repo, the gate
round-trip, the contracts-vs-reality cross-check, and the repo gate
itself (this test IS the tier-1 wiring, next to test_lint.py /
test_graphcheck.py).

tpu9: wirecheck-fixture-corpus — the string literals below are seeded
violations and fixture routes/metrics, not uses of the real wire
surfaces; the scanner skips this file entirely.
"""

import ast
import functools
import json
import os
import re
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import wire_gate  # noqa: E402

from tpu9.analysis import tomlmini  # noqa: E402
from tpu9.analysis.findings import Baseline, load_baseline  # noqa: E402
from tpu9.analysis.wirecheck import run_wirecheck  # noqa: E402
from tpu9.analysis.wirecheck import extract as wex  # noqa: E402


# -- mini repo ---------------------------------------------------------------

CLEAN_CONTRACTS = """\
[surface.mini]
producers = ["tpu9/prod.py::Engine.stats::out"]
consumers = ["tpu9/cons.py::consume::out"]
fields = ["alpha", "beta"]

[metrics]
entity_labels = ["container"]
assert_ok = ["tpu9_mini_rss_mb: per-container gauge, scraped not asserted"]

[keys.mini_loc]
pattern = "mini:loc:*"
writers = ["tpu9/"]
ttl = "required"

[env.TPU9_MINI_FLAG]
readers = ["tpu9/env_use.py"]
"""


def _mini_repo(tmp_path):
    (tmp_path / "scripts").mkdir()
    pkg = tmp_path / "tpu9"
    pkg.mkdir()
    (pkg / "prod.py").write_text(textwrap.dedent("""\
        class Engine:
            def stats(self):
                out = {}
                out["alpha"] = 1
                out["beta"] = 2
                return out
    """))
    (pkg / "cons.py").write_text(textwrap.dedent("""\
        def consume(out):
            return out["alpha"] + out["beta"]
    """))
    (pkg / "metrics_use.py").write_text(textwrap.dedent("""\
        def sample(metrics, cid):
            metrics.set_gauge("tpu9_mini_rss_mb", 1.0, {"container": cid})
            metrics.inc("tpu9_mini_requests", 1)

        def forget(metrics, cid):
            metrics.remove_gauge("tpu9_mini_rss_mb", {"container": cid})
    """))
    (pkg / "store_use.py").write_text(textwrap.dedent("""\
        async def write(store, wid):
            await store.set(f"mini:loc:{wid}", "x", ttl=30)
    """))
    (pkg / "env_use.py").write_text(textwrap.dedent("""\
        import os

        def flag():
            return os.environ.get("TPU9_MINI_FLAG", "0")
    """))
    (pkg / "rpc_srv.py").write_text(textwrap.dedent("""\
        def routes(r, h):
            r.add_post("/rpc/mini/run", h)
    """))
    (pkg / "rpc_cli.py").write_text(textwrap.dedent("""\
        def call(c):
            return c.request("POST", "/rpc/mini/run")
    """))
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_mini.py").write_text(textwrap.dedent("""\
        def test_requests(snapshot):
            assert "tpu9_mini_requests" in snapshot
    """))
    (tmp_path / "contracts.toml").write_text(CLEAN_CONTRACTS)
    return tmp_path


def _check(root, **kw):
    return run_wirecheck(str(root),
                         contracts_path=str(root / "contracts.toml"), **kw)


def _gate(root, *extra):
    return wire_gate.main(["--repo-root", str(root),
                           "--contracts", "contracts.toml", *extra])


def test_mini_repo_is_clean(tmp_path):
    res = _check(_mini_repo(tmp_path))
    assert res.parse_errors == []
    assert res.findings == [], [f.format() for f in res.findings]
    assert res.warnings == []
    assert _gate(tmp_path) == 0


# -- one seeded violation per rule, each must redden the gate ----------------

def test_wir001_phantom_consumer(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "cons.py").write_text(textwrap.dedent("""\
        def consume(out):
            return out["alpha"] + out["gamma"]
    """))
    res = _check(root)
    assert any(f.rule == "WIR001" and f.symbol == "gamma"
               for f in res.findings)
    assert _gate(root) == 1


def test_wir001_producer_drift(tmp_path):
    """Renaming a produced field trips BOTH sides: the contract entry
    nothing produces and the undeclared new name."""
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "prod.py").write_text(textwrap.dedent("""\
        class Engine:
            def stats(self):
                out = {}
                out["alpha"] = 1
                out["beta_renamed"] = 2
                return out
    """))
    res = _check(root)
    syms = {f.symbol for f in res.findings if f.rule == "WIR001"}
    assert "mini.beta" in syms          # contract rot
    assert "beta_renamed" in syms       # undeclared production
    assert "beta" in syms               # phantom consumer read
    assert _gate(root) == 1


def test_wir001_dead_telemetry_warns_not_gates(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "cons.py").write_text(textwrap.dedent("""\
        def consume(out):
            return out["alpha"]
    """))
    res = _check(root)
    assert res.findings == []
    assert any(w.rule == "WIR001" and w.symbol == "beta"
               for w in res.warnings)
    assert _gate(root) == 0             # warn tier never gates


def test_wir002_ghost_assert(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tests" / "test_mini.py").write_text(textwrap.dedent("""\
        def test_requests(snapshot):
            assert "tpu9_mini_ghost" in snapshot
    """))
    res = _check(root)
    assert any(f.rule == "WIR002" and f.symbol == "tpu9_mini_ghost"
               for f in res.findings)
    assert _gate(root) == 1


def test_wir002_an_interval_told_twice_is_an_emission(tmp_path):
    """``tracer.record_interval(span, registry, summary, ...)`` observes
    ``summary`` (ISSUE 41): a test may assert the series, and the same
    call with another name leaves the assertion a ghost."""
    root = _mini_repo(tmp_path)
    (root / "tests" / "test_mini.py").write_text(textwrap.dedent("""\
        def test_requests(snapshot):
            assert "tpu9_mini_hop_s" in snapshot
    """))
    for summary, clean in (("tpu9_mini_hop_s", True),
                           ("tpu9_mini_other_s", False)):
        (root / "tpu9" / "hop.py").write_text(textwrap.dedent(f"""\
            def hop(tracer, registry, anchor, t0, t1, trace):
                return tracer.record_interval(
                    "mini.hop", registry, "{summary}", anchor, t0, t1,
                    trace=trace)
        """))
        res = _check(root)
        ghosts = [f for f in res.findings if f.rule == "WIR002"
                  and f.symbol == "tpu9_mini_hop_s"]
        assert (ghosts == []) == clean, [f.format() for f in res.findings]


def test_wir002_gauge_without_remove(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "metrics_use.py").write_text(textwrap.dedent("""\
        def sample(metrics, cid):
            metrics.set_gauge("tpu9_mini_rss_mb", 1.0, {"container": cid})
            metrics.inc("tpu9_mini_requests", 1)
    """))
    res = _check(root)
    assert any(f.rule == "WIR002" and f.symbol == "tpu9_mini_rss_mb"
               for f in res.findings)
    assert _gate(root) == 1


def test_key001_undeclared_namespace(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "store2.py").write_text(textwrap.dedent("""\
        async def rogue(store):
            await store.set("rogue:k:1", "v")
    """))
    res = _check(root)
    assert any(f.rule == "KEY001" and f.symbol.startswith("rogue:")
               for f in res.findings)
    assert _gate(root) == 1


def test_key001_ttl_discipline(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "store_use.py").write_text(textwrap.dedent("""\
        async def write(store, wid):
            await store.set(f"mini:loc:{wid}", "x")
    """))
    res = _check(root)
    assert any(f.rule == "KEY001" and "TTL" in f.message
               for f in res.findings)
    assert _gate(root) == 1


def test_env001_divergent_default(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "env2.py").write_text(textwrap.dedent("""\
        import os

        def flag():
            return os.environ.get("TPU9_MINI_FLAG", "1")
    """))
    res = _check(root)
    rules = [f for f in res.findings if f.rule == "ENV001"]
    assert any("outside its declared readers" in f.message for f in rules)
    assert any("divergent" in f.message for f in rules)
    assert _gate(root) == 1


def test_env001_undeclared_var(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "env2.py").write_text(textwrap.dedent("""\
        import os

        def other():
            return os.environ.get("TPU9_MINI_OTHER")
    """))
    res = _check(root)
    assert any(f.rule == "ENV001" and f.symbol == "TPU9_MINI_OTHER"
               for f in res.findings)
    assert _gate(root) == 1


def test_rpc001_dead_handler_and_orphan_call(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "rpc_srv.py").write_text(textwrap.dedent("""\
        def routes(r, h):
            r.add_post("/rpc/mini/run", h)
            r.add_get("/rpc/mini/dead", h)
    """))
    res = _check(root)
    assert any(f.rule == "RPC001" and f.symbol == "/rpc/mini/dead"
               for f in res.findings)
    (root / "tpu9" / "rpc_srv.py").write_text(textwrap.dedent("""\
        def routes(r, h):
            r.add_post("/rpc/mini/run", h)
    """))
    (root / "tpu9" / "rpc_cli.py").write_text(textwrap.dedent("""\
        def call(c):
            return c.request("POST", "/rpc/mini/orphan")
    """))
    res = _check(root)
    assert any(f.rule == "RPC001" and f.symbol == "/rpc/mini/orphan"
               for f in res.findings)
    assert _gate(root) == 1


def test_fixture_corpus_pragma_skips_file(tmp_path):
    """A file marked ``tpu9: wirecheck-fixture-corpus`` in its head is
    excluded from inventory extraction — its strings are data."""
    root = _mini_repo(tmp_path)
    (root / "tests" / "test_fixtures.py").write_text(
        '"""tpu9: wirecheck-fixture-corpus"""\n'
        'GHOST = "tpu9_mini_ghost2"\n'
        'ROUTE = "/rpc/mini/never"\n')
    res = _check(root)
    assert res.findings == [], [f.format() for f in res.findings]


def test_route_match_prefix_semantics():
    """Call-side patterns from f-strings/concats prefix-match longer
    registered routes; registered patterns never prefix-match."""
    assert wex.route_match("/rpc/pod/*/exec", "/rpc/pod/**")
    assert wex.route_match("/rpc/pod/*/proc/*", "/rpc/pod/")
    assert wex.route_match("/api/v1/machine", "/api/v1/machine*")
    assert wex.route_match("/api/v1/machine/*/logs", "/api/v1/machine*")
    assert not wex.route_match("/rpc/other/x", "/rpc/pod/")
    assert not wex.route_match("/rpc/pod", "/rpc/pod/extra")
    assert wex.route_match("/rpc/deploy", "/rpc/deploy")
    assert not wex.route_match("/rpc/deploy", "/rpc/deplo")


# -- gate round-trip ---------------------------------------------------------

def test_gate_round_trip(tmp_path, capsys):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "store2.py").write_text(
        "async def rogue(store):\n"
        "    await store.set(\"rogue:k:1\", \"v\")\n")
    rc = _gate(root)
    out = capsys.readouterr().out
    assert rc == 1 and "KEY001" in out and "NEW" in out

    # triage into the baseline -> green
    assert _gate(root, "--update-baseline", "--reason",
                 "test debt, reviewed") == 0
    assert _gate(root) == 0

    # fixing leaves a stale entry; --strict-stale ratchets it out
    (root / "tpu9" / "store2.py").write_text("")
    assert _gate(root) == 0
    assert _gate(root, "--strict-stale") == 1


def test_gate_rejects_reasonless_update(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "env2.py").write_text(
        "import os\nX = os.environ.get(\"TPU9_MINI_OTHER\")\n")
    assert _gate(root, "--update-baseline") == 2


def test_scoped_update_preserves_out_of_scope_entries(tmp_path):
    """A --roots-narrowed baseline update must not destroy triage the
    narrowed run never saw (the tpu9lint PR 14 regression class)."""
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "a").mkdir()
    (root / "tpu9" / "b").mkdir()
    (root / "tpu9" / "a" / "bad.py").write_text(
        "async def w(store):\n"
        "    await store.set(\"roguea:k\", 1)\n")
    (root / "tpu9" / "b" / "bad.py").write_text(
        "async def w(store):\n"
        "    await store.set(\"rogueb:k\", 1)\n")
    assert _gate(root, "--update-baseline", "--reason", "debt") == 0

    bl_path = root / "scripts" / "wire_baseline.json"
    before = Baseline.load(str(bl_path))
    assert len(before.entries) == 2

    # fix a's violation, update scoped to tpu9/a: a's entry pruned,
    # b's (out of scope) preserved
    (root / "tpu9" / "a" / "bad.py").write_text("")
    assert _gate(root, "--roots", "tpu9/a",
                 "--update-baseline", "--reason", "debt") == 0
    after = Baseline.load(str(bl_path))
    assert len(after.entries) == 1
    assert all(e["path"] == "tpu9/b/bad.py" for e in after.entries.values())
    assert _gate(root) == 0


def test_scoped_run_filters_stale_reporting(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "b").mkdir()
    (root / "tpu9" / "b" / "bad.py").write_text(
        "async def w(store):\n"
        "    await store.set(\"rogueb:k\", 1)\n")
    assert _gate(root, "--update-baseline", "--reason", "debt") == 0
    (root / "tpu9" / "b" / "bad.py").write_text("")
    # the entry is stale repo-wide, but a run scoped elsewhere must not
    # claim (or strict-fail on) staleness it cannot see
    assert _gate(root, "--roots", "tpu9/a", "--strict-stale") == 0
    assert _gate(root, "--strict-stale") == 1


# -- json schema -------------------------------------------------------------

def test_json_schema_round_trip(tmp_path, capsys):
    from tpu9.analysis.wirecheck.__main__ import main as wiremain
    root = _mini_repo(tmp_path)
    (root / "tpu9" / "env2.py").write_text(
        "import os\nX = os.environ.get(\"TPU9_MINI_OTHER\")\n")
    rc = wiremain(["--repo-root", str(root), "--contracts",
                   "contracts.toml", "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["version"] == 1 and payload["tool"] == "wirecheck"
    rec = [r for r in payload["findings"] if r["status"] == "new"][0]
    assert {"file", "line", "col", "rule", "symbol", "message",
            "fingerprint", "status"} <= set(rec)


# -- contracts.toml vs reality (independent extractor) -----------------------

def _qualnames(tree):
    """Independently-written qualname walker (no wirecheck imports)."""
    out = {}

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qual = f"{prefix}{child.name}" if prefix else child.name
                out[qual] = child
                visit(child, qual + ".")
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


def _scope_mentions_var(node, var):
    if var.startswith("self."):
        attr = var.split(".", 1)[1]
        return any(isinstance(n, ast.Attribute) and n.attr == attr
                   for n in ast.walk(node))
    return any(isinstance(n, ast.Name) and n.id == var
               for n in ast.walk(node)) or \
        any(isinstance(n, ast.arg) and n.arg == var
            for n in ast.walk(node))


def test_contracts_scopes_exist_in_real_code():
    """Every declared producer/consumer scope must resolve against the
    real tree — a refactor that moves a scope shows up here even if the
    checker would only report it as 'contracts stale'."""
    raw = tomlmini.load_file(
        os.path.join(REPO, "tpu9", "analysis", "contracts.toml"))
    assert raw.get("surface"), "no surfaces declared"
    for sname, surf in raw["surface"].items():
        scopes = list(surf.get("producers", [])) + \
            list(surf.get("consumers", []))
        assert scopes, f"surface {sname} declares no scopes"
        for entry in scopes:
            path, qual, var = entry.split("::")
            full = os.path.join(REPO, path)
            assert os.path.exists(full), entry
            with open(full, encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
            quals = _qualnames(tree)
            assert qual in quals, entry
            assert _scope_mentions_var(quals[qual], var), entry
        for entry in surf.get("consumer_lists", []):
            path, const = entry.split("::")
            with open(os.path.join(REPO, path), encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
            assert any(isinstance(n, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == const
                for t in n.targets) for n in ast.walk(tree)), entry


def test_contracts_env_readers_exist():
    raw = tomlmini.load_file(
        os.path.join(REPO, "tpu9", "analysis", "contracts.toml"))
    for var, t in raw.get("env", {}).items():
        assert var.startswith("TPU9_"), var
        for rd in t.get("readers", []):
            assert os.path.exists(os.path.join(REPO, rd)), (var, rd)


@pytest.mark.parametrize("checker", ["tpu9.analysis.runner",
                                     "tpu9.analysis.wirecheck"])
def test_default_roots_are_in_the_checkout(checker):
    """Every root a checker scans by default exists: a root that names a
    deleted file is walked as nothing, in silence, and the gate goes on
    reporting a clean scan of a tree it no longer covers."""
    import importlib
    roots = importlib.import_module(checker).DEFAULT_ROOTS
    assert roots and "tpu9" in roots
    gone = [r for r in roots if not os.path.exists(os.path.join(REPO, r))]
    assert not gone, gone


def test_contracts_justifications_name_files_that_exist():
    """A reason that says "asserted in tests/x.py" or "read by
    benchmark/y.py" is a claim about the tree: every repo path a string
    or comment of contracts.toml names must exist (a test module may go
    without its ``.py``). Would have caught metrics "guarded by" a script
    that was about to go."""
    with open(os.path.join(REPO, "tpu9", "analysis", "contracts.toml"),
              encoding="utf-8") as fh:
        text = fh.read()
    named = set(re.findall(
        r"(?<![\w/.-])((?:tpu9|tests|scripts|benchmark|deploy|examples"
        r"|native)/[\w./-]*\w)", text))
    assert len(named) > 20, named
    gone = sorted(n for n in named
                  if not os.path.exists(os.path.join(REPO, n))
                  and not os.path.exists(os.path.join(REPO, n + ".py")))
    assert not gone, gone


# files a document may name that no checkout holds, and why
_WRITTEN_AT_RUN_TIME = {
    "device_scopes.json",       # beside a runner's profile dump
    "index.json",               # inside a saved .tpu9w weights directory
    "wire_baseline.json",       # wire_gate.py --update-baseline; absent = empty
}


def _braces(token: str) -> list:
    m = re.search(r"\{([^}]*)\}", token)
    if not m:
        return [token]
    return [out for alt in m.group(1).split(",")
            for out in _braces(token[:m.start()] + alt + token[m.end():])]


@functools.lru_cache(maxsize=None)
def _checkout_files() -> frozenset:
    tree = set()
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs if not x.startswith(".")
                   and x not in ("__pycache__", "chiprun_out")]
        tree.update(os.path.relpath(os.path.join(d, f), REPO)
                    for f in files)
    return frozenset(tree)


@pytest.mark.parametrize("doc", ["README.md", "ARCHITECTURE.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_name_files_that_exist(doc):
    """Every backticked ``*.py`` / ``*.json`` path in the documents a new
    owner reads first is the tail of a file in the checkout (``a/{b,c}.py``
    names two): a document that sends its reader to a deleted script is
    caught here and not by the reader."""
    tree = _checkout_files()
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        named = set(re.findall(r"`([^`\s]+\.(?:py|json))`", fh.read()))
    assert named, doc
    gone = sorted(
        path for token in named if not token.startswith("/")
        for path in _braces(token)
        if os.path.basename(path) not in _WRITTEN_AT_RUN_TIME
        and not any(f == path or f.endswith("/" + path) for f in tree))
    assert not gone, gone


def test_contracts_external_routes_are_registered():
    """external_ok declares a route exists but is called from outside the
    repo — the route must still be *registered*, independently scanned."""
    raw = tomlmini.load_file(
        os.path.join(REPO, "tpu9", "analysis", "contracts.toml"))
    entries = raw.get("rpc", {}).get("external_ok", [])
    if not entries:
        return
    registered = set()
    gw = os.path.join(REPO, "tpu9", "gateway", "gateway.py")
    with open(gw, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr.startswith("add_"):
            for a in node.args:
                if isinstance(a, ast.Constant) and isinstance(a.value, str) \
                        and a.value.startswith("/"):
                    import re
                    registered.add(re.sub(r"\{[^}]*\}", "*", a.value))
    for e in entries:
        route = e.split(":", 1)[0].strip()
        assert route in registered, route


def test_analysis_all_static_only(capsys):
    """``python -m tpu9.analysis --all`` (satellite): every static plane
    behind one exit code and one JSON stream."""
    from tpu9.analysis.__main__ import main as amain
    rc = amain(["--all", "--static-only", "--format", "json",
                "--repo-root", REPO])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["tools"] == ["tpu9lint", "wirecheck"]
    assert payload["parse_errors"] == []
    for rec in payload["findings"]:
        assert rec["status"] == "baselined"
        assert rec["tool"] in ("tpu9lint", "wirecheck")


# -- regressions: real drift bugs surfaced by the checker --------------------

def test_worker_prunes_rss_gauges_for_reaped_containers():
    """WIR002 regression: the per-container RSS gauge must be removed
    when the container leaves the police set, or the series leaks
    fleet-wide for the worker's whole lifetime."""
    from tpu9.observability import Metrics
    from tpu9.worker.worker import Worker

    class _W:
        _prune_rss_gauges = Worker._prune_rss_gauges

    w, m = _W(), Metrics()
    m.set_gauge("tpu9_container_rss_mb", 64.0, {"container": "c1"})
    m.set_gauge("tpu9_container_rss_mb", 32.0, {"container": "c2"})
    w._prune_rss_gauges({"c1", "c2"}, m)      # both still policed
    assert len(m.gauges) == 2
    w._prune_rss_gauges({"c1"}, m)            # c2 reaped
    assert list(m.gauges) == ['tpu9_container_rss_mb{container="c1"}']
    w._prune_rss_gauges(set(), m)             # all gone
    assert m.gauges == {}


def test_gateway_registers_no_serve_rpc():
    """RPC001 regression: the dead /rpc/serve handler is gone — serve
    sessions ride /rpc/deploy (see tpu9/cli/main.py serve)."""
    from tpu9.gateway.gateway import Gateway
    gw_path = os.path.join(REPO, "tpu9", "gateway", "gateway.py")
    with open(gw_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    registered = {a.value for node in ast.walk(tree)
                  if isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr.startswith("add_")
                  for a in node.args
                  if isinstance(a, ast.Constant)
                  and isinstance(a.value, str) and a.value.startswith("/")}
    assert "/rpc/serve" not in registered
    assert "/rpc/deploy" in registered
    assert not hasattr(Gateway, "_rpc_serve")


# -- the repo gate -----------------------------------------------------------

def test_repo_is_wire_clean():
    """THE tier-1 gate: zero new wire findings on the repo, fast enough
    for the fast suite (acceptance: full run < 60 s)."""
    res = run_wirecheck(REPO)
    assert res.parse_errors == []
    bl = load_baseline(os.path.join(REPO, "scripts", "wire_baseline.json"))
    new, _known, stale = bl.split(res.findings)
    assert new == [], "\n".join(f.format() for f in new)
    assert stale == [], "stale wire-baseline entries: " + str(stale)
    assert res.elapsed_s < 60.0
