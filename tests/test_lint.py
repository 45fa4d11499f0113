"""tpu9lint (ISSUE 7): rule fixtures, suppression/baseline round-trips, the
boundaries.toml-vs-reality check, and the repo gate itself (this test IS the
tier-1 wiring)."""

import ast
import json
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import lint_gate  # noqa: E402

from tpu9.analysis import run_analysis  # noqa: E402
from tpu9.analysis import boundaries as bnd  # noqa: E402
from tpu9.analysis import rules  # noqa: E402
from tpu9.analysis import tomlmini  # noqa: E402
from tpu9.analysis.findings import (Baseline, apply_suppressions,  # noqa: E402
                                    parse_suppressions)


def check(src: str, path: str = "mod.py"):
    tree = ast.parse(textwrap.dedent(src))
    return rules.check_file(path, tree)


def rule_ids(src: str):
    return sorted({f.rule for f in check(src)})


# -- per-rule fixtures: positive AND negative --------------------------------

class TestASY001:
    def test_wait_for_queue_get_flagged(self):
        src = """
        import asyncio
        async def poll(sub):
            while True:
                msg = await asyncio.wait_for(sub.get(), 1.0)
        """
        fs = [f for f in check(src) if f.rule == "ASY001"]
        assert len(fs) == 1
        assert "poll loop" in fs[0].message

    def test_wait_for_event_wait_flagged(self):
        src = """
        import asyncio
        async def gate(ev):
            await asyncio.wait_for(ev.wait(), timeout=15.0)
        """
        assert "ASY001" in rule_ids(src)

    def test_bare_get_and_shield_not_flagged(self):
        src = """
        import asyncio
        async def ok(sub, ev):
            msg = await sub.get()
            await asyncio.wait_for(asyncio.shield(ev.wait()), 1.0)
            await asyncio.wait_for(some_coro(), 1.0)
        """
        assert "ASY001" not in rule_ids(src)


class TestASY002:
    def test_discarded_create_task_flagged(self):
        src = """
        import asyncio
        from asyncio import create_task
        async def fire(loop):
            asyncio.create_task(pump())
            loop.create_task(pump())
            asyncio.ensure_future(pump())
            create_task(pump())     # bare from-import: same weak-ref bug
        """
        assert len([f for f in check(src) if f.rule == "ASY002"]) == 4

    def test_stored_or_awaited_not_flagged(self):
        src = """
        import asyncio
        async def ok(tasks):
            t = asyncio.create_task(pump())
            tasks.add(asyncio.create_task(pump()))
            await asyncio.create_task(pump())
            return asyncio.create_task(pump())
        """
        assert "ASY002" not in rule_ids(src)


class TestASY003:
    def test_swallowing_handlers_flagged(self):
        src = """
        import asyncio
        async def bad1():
            try:
                await work()
            except BaseException:
                pass
        async def bad2():
            try:
                await work()
            except asyncio.CancelledError:
                return None
        async def bad3():
            try:
                await work()
            except:
                log()
        """
        assert len([f for f in check(src) if f.rule == "ASY003"]) == 3

    def test_raise_inside_nested_def_does_not_silence(self):
        src = """
        import asyncio
        async def bad():
            try:
                await work()
            except BaseException:
                def helper():
                    raise RuntimeError("not OUR re-raise")
                helper()
        """
        assert "ASY003" in rule_ids(src)

    def test_reraise_and_sync_not_flagged(self):
        src = """
        import asyncio
        async def ok1():
            try:
                await work()
            except BaseException:
                cleanup()
                raise
        async def ok2():
            try:
                await work()
            except Exception:
                pass
        def sync_ok():
            try:
                work()
            except BaseException:
                pass
        """
        assert "ASY003" not in rule_ids(src)


class TestASY004:
    def test_blocking_calls_flagged(self):
        src = """
        import time, subprocess, shutil
        async def bad():
            time.sleep(1)
            subprocess.run(["ls"])
            shutil.rmtree("/tmp/x")
            with open("f") as f:
                pass
        """
        assert len([f for f in check(src) if f.rule == "ASY004"]) == 4

    def test_sync_def_and_nested_sync_not_flagged(self):
        src = """
        import time, asyncio
        def sync():
            time.sleep(1)
        async def ok():
            def inner():
                time.sleep(1)      # runs via to_thread
            await asyncio.to_thread(inner)
            await asyncio.sleep(1)
        """
        assert "ASY004" not in rule_ids(src)


class TestJAX002:
    def test_inline_jit_and_jit_in_loop_flagged(self):
        src = """
        import jax
        def bad(x, fns):
            y = jax.jit(f)(x)
            for i in range(3):
                fns.append(jax.jit(g))
        """
        assert len([f for f in check(src) if f.rule == "JAX002"]) == 2

    def test_cached_jit_not_flagged(self):
        src = """
        import jax
        compiled = jax.jit(f)
        class M:
            def get(self):
                fn = self._c["k"] = jax.jit(g)
                return fn
        """
        assert "JAX002" not in rule_ids(src)


class TestOBS001:
    """time.time() arithmetic for durations/deadlines in hot-path planes
    (ISSUE 8 satellite — the trace.py durationMs NTP-step bug class)."""

    PATH = "tpu9/serving/engine.py"

    def test_direct_arithmetic_and_compare_flagged(self):
        src = """
        import time
        def shed(deadline):
            deadline = time.time() + 30.0
            if time.time() > deadline:
                return True
        """
        fs = [f for f in check(src, path=self.PATH) if f.rule == "OBS001"]
        assert len(fs) == 2
        assert "monotonic" in fs[0].message

    def test_tainted_local_name_flagged(self):
        src = """
        import time
        def measure(fn):
            t0 = time.time()
            fn()
            return time.time() - t0
        """
        fs = [f for f in check(src, path=self.PATH) if f.rule == "OBS001"]
        assert fs, "wall-wall subtraction must be flagged"

    def test_tainted_attribute_flagged_file_wide(self):
        # the ORIGINAL trace.py bug: start stored from time.time() in one
        # method, subtracted in another
        src = """
        import time
        class Span:
            def __init__(self):
                self.start = time.time()
            def duration(self, end):
                return end - self.start
        """
        fs = [f for f in check(src, path=self.PATH) if f.rule == "OBS001"]
        assert len(fs) == 1
        assert fs[0].symbol == "Span.duration"

    def test_monotonic_and_anchor_not_flagged(self):
        src = """
        import time
        class Span:
            def __init__(self):
                self.start = time.time()       # wall ANCHOR: stored only
                self.t0 = time.monotonic()
            def duration(self):
                return time.monotonic() - self.t0
            def start_nanos(self):
                return int(self.start * 1e9)   # epoch conversion (mult)
        """
        assert "OBS001" not in {f.rule
                                for f in check(src, path=self.PATH)}

    def test_parallel_tuple_assign_taints_only_wall_half(self):
        src = """
        import time
        def f():
            t_mono, t_wall = time.monotonic(), time.time()
            ok = time.monotonic() - t_mono
            bad = 5.0 + t_wall
            return ok, bad
        """
        fs = [f for f in check(src, path=self.PATH) if f.rule == "OBS001"]
        assert len(fs) == 1 and "t_wall" in fs[0].message

    def test_out_of_scope_path_not_flagged(self):
        src = """
        import time
        def paid_deadline():
            return time.time() + 600.0   # store-persisted epoch (gateway)
        """
        assert check(src, path="tpu9/gateway/gateway.py") == []

    def test_lambda_bodies_are_scanned(self):
        # lambdas are scopes of their own (excluded from the enclosing
        # scan) — wall arithmetic inside one must still be flagged
        src = """
        import time
        f = lambda t0: time.time() - t0
        def waiter(deadline):
            expired = lambda: time.time() > deadline
            return expired
        """
        fs = [f for f in check(src, path=self.PATH) if f.rule == "OBS001"]
        assert len(fs) == 2
        assert {f.symbol for f in fs} == {"<lambda>", "waiter.<lambda>"}

    def test_monotonic_lambda_not_flagged(self):
        src = """
        import time
        def waiter(deadline_mono):
            return lambda: time.monotonic() > deadline_mono
        """
        assert "OBS001" not in {f.rule
                                for f in check(src, path=self.PATH)}


class TestOBS002:
    """Unbounded metric-label cardinality (ISSUE 12 satellite): request/
    trace/prompt identity as a metrics.inc/observe/set_gauge label value
    mints a permanent registry series per request."""

    def test_request_id_label_flagged(self):
        src = """
        from tpu9.observability import metrics
        def record(request_id):
            metrics.inc("tpu9_requests_total",
                        labels={"request": request_id})
        """
        fs = [f for f in check(src) if f.rule == "OBS002"]
        assert len(fs) == 1
        assert "request_id" in fs[0].message

    def test_trace_id_fstring_and_attribute_flagged(self):
        src = """
        from tpu9.observability import metrics
        def record(req, ctx):
            metrics.observe("tpu9_lat_s", 0.1,
                            labels={"t": f"trace-{ctx.trace_id}"})
            metrics.set_gauge("tpu9_depth", 1,
                              labels={"r": req.request_id})
        """
        assert len([f for f in check(src) if f.rule == "OBS002"]) == 2

    def test_prompt_and_minted_id_flagged(self):
        src = """
        from tpu9.observability import metrics
        from tpu9.observability.trace import new_trace_id
        def record(prompt):
            metrics.inc("hits", labels={"p": prompt[:64]})
            metrics.inc("spans", labels={"id": new_trace_id()})
        """
        assert len([f for f in check(src) if f.rule == "OBS002"]) == 2

    def test_self_metrics_receiver_and_positional_labels_flagged(self):
        src = """
        class Engine:
            def _obs(self, req):
                self.metrics.observe("tpu9_engine_ttft_s", 0.2,
                                     {"request": req.request_id})
        """
        assert len([f for f in check(src) if f.rule == "OBS002"]) == 1

    def test_bounded_labels_not_flagged(self):
        src = """
        from tpu9.observability import metrics
        def record(stub_id, tenant, reason, worker_id, phase):
            metrics.inc("tpu9_router_shed_total",
                        labels={"stub": stub_id, "reason": reason})
            metrics.observe("tpu9_router_queue_wait_s", 0.1,
                            labels={"tenant": tenant})
            metrics.set_gauge("tpu9_startup_phase_s", 1.0,
                              labels={"worker": worker_id, "phase": phase})
        """
        assert "OBS002" not in rule_ids(src)

    def test_non_metrics_receiver_not_flagged(self):
        src = """
        def record(store, request_id):
            store.inc("hits", labels={"request": request_id})
            attrs = {"request": request_id}     # span attrs are the
            span.set_attrs(attrs)               # CORRECT home for ids
        """
        assert "OBS002" not in rule_ids(src)


class TestTMO001:
    """ISSUE 15: network-facing awaits without a timeout/deadline in the
    gateway/router/runner/worker/cache/statestore planes."""

    PATH = "tpu9/gateway/mod.py"

    def ids(self, src, path=None):
        tree = ast.parse(textwrap.dedent(src))
        return sorted({f.rule
                       for f in rules.check_file(path or self.PATH, tree)})

    def test_awaited_http_call_without_timeout_flagged(self):
        src = """
        async def ship(session, url):
            await session.post(url, json={})
        """
        assert "TMO001" in self.ids(src)

    def test_async_with_http_call_without_timeout_flagged(self):
        # the dominant aiohttp idiom: the request awaits in __aenter__,
        # not through an Await node
        src = """
        async def ship(session, url):
            async with session.post(url, json={}) as resp:
                return await resp.read()
        """
        assert "TMO001" in self.ids(src)

    def test_timeout_kwarg_satisfies(self):
        src = """
        import aiohttp
        async def ship(session, url):
            await session.post(url, json={},
                               timeout=aiohttp.ClientTimeout(total=5))
            async with session.get(url, timeout=5.0) as resp:
                return await resp.read()
        """
        assert "TMO001" not in self.ids(src)

    def test_direct_open_connection_flagged_wrapped_not(self):
        src = """
        import asyncio
        async def dial(host, port):
            r, w = await asyncio.open_connection(host, port)
        async def dial_bounded(host, port):
            r, w = await asyncio.wait_for(
                asyncio.open_connection(host, port), 5.0)
        """
        fs = [f for f in rules.check_file(
            self.PATH, ast.parse(textwrap.dedent(src)))
            if f.rule == "TMO001"]
        assert len(fs) == 1
        assert fs[0].symbol == "dial"

    def test_blocking_store_read_without_timeout_flagged(self):
        src = """
        async def drain(store, key):
            item = await store.blpop(key)
            evs = await store.xread(key, "0")
        """
        fs = [f for f in rules.check_file(
            self.PATH, ast.parse(textwrap.dedent(src)))
            if f.rule == "TMO001"]
        assert len(fs) == 2

    def test_blocking_store_read_with_timeout_ok(self):
        src = """
        async def drain(store, key):
            item = await store.blpop(key, 5.0)
            evs = await store.xread(key, "0", timeout=2.0)
        """
        assert "TMO001" not in self.ids(src)

    def test_out_of_scope_path_not_flagged(self):
        src = """
        async def ship(session, url):
            await session.post(url, json={})
        """
        assert "TMO001" not in self.ids(src, path="tpu9/sdk/client.py")

    def test_non_session_receiver_not_flagged(self):
        src = """
        async def run(queue, repo):
            await queue.get()
            await repo.get("key")
        """
        assert "TMO001" not in self.ids(src)


class TestJAX001:
    HOT = """
    import jax, numpy as np
    class Engine:
        def _serve_loop_inner(self):
            self._step()
            self._cold()   # not defined here: name-linked only to defs
        def _step(self):
            x = jax.device_get(self.buf)
            return np.asarray(x)
        def _warm(self):
            jax.device_get(self.buf)   # NOT reachable from the loop
    """

    def run(self, src):
        tree = ast.parse(textwrap.dedent(src))
        return rules.check_jax_hotpath({"hot.py": tree},
                                       ["_serve_loop_inner"])

    def test_reachable_syncs_flagged_unreachable_not(self):
        fs = self.run(self.HOT)
        assert {f.symbol for f in fs} == {"Engine._step"}
        assert len(fs) == 2   # device_get + np.asarray

    def test_item_and_block_until_ready(self):
        src = """
        def _serve_loop_inner(arr):
            n = arr.item()
            arr.block_until_ready()
        """
        assert len(self.run(src)) == 2


class TestBND001:
    TOML = """
    [allow]
    "tpu9.serving" = ["tpu9.ops"]
    [forbid]
    "tpu9.router" = ["tpu9.serving"]
    [restricted]
    "tpu9.ops.quant" = ["tpu9.ops", "tpu9.serving"]
    """

    def cfg(self):
        return bnd.BoundaryConfig(
            **{k: v for k, v in tomlmini.loads(
                textwrap.dedent(self.TOML)).items()})

    def run(self, path, src):
        tree = ast.parse(textwrap.dedent(src))
        return bnd.check_boundaries({path: tree}, self.cfg())

    def test_allow_violation(self):
        fs = self.run("tpu9/serving/engine.py",
                      "from tpu9.gateway import gateway")
        assert len(fs) == 1 and "contract" in fs[0].message

    def test_allow_ok_and_intra_package(self):
        assert self.run("tpu9/serving/engine.py", """
            from tpu9.ops import attention
            from . import spec
            from ..ops.quant import quantize_kv
        """) == []

    def test_forbid_and_relative_resolution(self):
        fs = self.run("tpu9/router/fleet.py", "from ..serving import engine")
        assert len(fs) == 1 and "forbidden" in fs[0].message

    def test_restricted(self):
        fs = self.run("tpu9/worker/worker.py",
                      "from tpu9.ops.quant import quantize_kv")
        assert len(fs) == 1 and "restricted" in fs[0].message


# -- suppressions & baseline -------------------------------------------------

class TestSuppressions:
    SRC = ("import asyncio\n"
           "async def f(sub):\n"
           "    await asyncio.wait_for(sub.get(), 1)"
           "  # tpu9: noqa[ASY001] reviewed: single-shot helper\n")

    def test_noqa_with_reason_suppresses(self):
        tree = ast.parse(self.SRC)
        fs = rules.check_file("m.py", tree)
        kept, supp = apply_suppressions(fs, parse_suppressions(self.SRC),
                                        "m.py")
        assert kept == [] and len(supp) == 1

    def test_noqa_without_reason_is_sup001_and_does_not_suppress(self):
        src = self.SRC.replace(" reviewed: single-shot helper", "")
        tree = ast.parse(src)
        fs = rules.check_file("m.py", tree)
        kept, supp = apply_suppressions(fs, parse_suppressions(src), "m.py")
        assert supp == []
        assert sorted(f.rule for f in kept) == ["ASY001", "SUP001"]

    def test_reasonless_noqa_in_clean_file_raises_sup001(self, tmp_path):
        """A dead/bare noqa in a file with NO findings must still surface
        (the ratchet would otherwise rot invisibly)."""
        root = _mini_repo(tmp_path)
        (root / "pkg" / "clean.py").write_text(
            "x = 1  # tpu9: noqa[ASY001]\n")
        res = run_analysis(str(root), roots=("pkg",))
        assert [f.rule for f in res.findings] == ["SUP001"]

    def test_comment_above_covers_next_line(self):
        src = ("import asyncio\n"
               "async def f(sub):\n"
               "    # tpu9: noqa[ASY001] reviewed: the caller re-cancels\n"
               "    await asyncio.wait_for(sub.get(), 1)\n")
        tree = ast.parse(src)
        kept, supp = apply_suppressions(
            rules.check_file("m.py", tree), parse_suppressions(src), "m.py")
        assert kept == [] and len(supp) == 1

    def test_end_of_line_noqa_does_not_leak_to_next_line(self):
        """A new finding added directly below an end-of-line suppression
        must NOT ride it — the ratchet stays tight for adjacent lines."""
        src = ("import asyncio\n"
               "async def f(a, b):\n"
               "    await asyncio.wait_for(a.get(), 1)"
               "  # tpu9: noqa[ASY001] reviewed: helper re-cancels\n"
               "    await asyncio.wait_for(b.get(), 1)\n")
        tree = ast.parse(src)
        kept, supp = apply_suppressions(
            rules.check_file("m.py", tree), parse_suppressions(src), "m.py")
        assert len(supp) == 1 and len(kept) == 1
        assert kept[0].line == 4


class TestBaseline:
    def test_round_trip_and_split(self, tmp_path):
        fs = check("""
        import asyncio
        async def f():
            asyncio.create_task(g())
        async def h():
            asyncio.create_task(g())
        """)
        bl = Baseline()
        bl.add(fs[0], "triaged: test debt")
        p = tmp_path / "bl.json"
        bl.save(str(p))
        bl2 = Baseline.load(str(p))
        new, known, stale = bl2.split(fs)
        assert [f.fingerprint for f in known] == [fs[0].fingerprint]
        assert [f.fingerprint for f in new] == [fs[1].fingerprint]
        assert stale == []
        new2, known2, stale2 = bl2.split([])
        assert new2 == [] and known2 == [] and len(stale2) == 1

    def test_reason_is_mandatory(self, tmp_path):
        p = tmp_path / "bl.json"
        p.write_text(json.dumps({"version": 1, "findings": [
            {"fingerprint": "aa", "rule": "ASY001", "path": "x.py",
             "status": "suppressed", "reason": "  "}]}))
        with pytest.raises(ValueError, match="no reason"):
            Baseline.load(str(p))

    def test_occurrence_keeps_same_site_distinct(self):
        from tpu9.analysis.findings import assign_occurrences
        fs = assign_occurrences(check("""
        import asyncio
        async def f():
            asyncio.create_task(g())
            asyncio.create_task(g())
        """))
        assert len({f.fingerprint for f in fs}) == 2


# -- the gate ----------------------------------------------------------------

def _mini_repo(tmp_path):
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text("import asyncio\n")
    (tmp_path / "scripts").mkdir()
    return tmp_path


def test_gate_fails_on_injected_asy001(tmp_path, capsys):
    root = _mini_repo(tmp_path)
    (root / "pkg" / "bad.py").write_text(
        "import asyncio\n"
        "async def poll(sub):\n"
        "    while True:\n"
        "        await asyncio.wait_for(sub.get(), 1.0)\n")
    rc = lint_gate.main(["--repo-root", str(root), "--roots", "pkg"])
    out = capsys.readouterr().out
    assert rc == 1 and "ASY001" in out and "NEW" in out

    # triage it into the baseline -> gate goes green
    rc = lint_gate.main(["--repo-root", str(root), "--roots", "pkg",
                         "--update-baseline", "--reason",
                         "test debt, reviewed"])
    assert rc == 0
    rc = lint_gate.main(["--repo-root", str(root), "--roots", "pkg"])
    assert rc == 0

    # fixing the bug leaves a stale entry; --strict-stale ratchets it out
    (root / "pkg" / "bad.py").write_text("import asyncio\n")
    assert lint_gate.main(["--repo-root", str(root), "--roots", "pkg"]) == 0
    assert lint_gate.main(["--repo-root", str(root), "--roots", "pkg",
                           "--strict-stale"]) == 1


def test_gate_rejects_reasonless_update(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "pkg" / "bad.py").write_text(
        "import time\nasync def f():\n    time.sleep(1)\n")
    rc = lint_gate.main(["--repo-root", str(root), "--roots", "pkg",
                         "--update-baseline"])
    assert rc == 2


def test_repo_is_lint_clean():
    """THE tier-1 gate: zero new findings on the repo, and fast enough to
    live in the fast suite (acceptance: full run < 60 s)."""
    result = run_analysis(REPO)
    bl = Baseline.load(os.path.join(REPO, "scripts", "lint_baseline.json"))
    new, _known, stale = bl.split(result.findings)
    assert result.parse_errors == []
    assert new == [], "\n".join(f.format() for f in new)
    assert stale == [], "stale baseline entries: " + str(stale)
    assert result.elapsed_s < 60.0
    # every shipped rule has recorded triage: a fix or a suppression
    triaged = {e["rule"] for e in bl.entries.values()}
    triaged |= {e["rule"] for e in bl.fixed}
    triaged |= {f.rule for f in result.suppressed}
    assert {"ASY001", "ASY002", "ASY003", "ASY004",
            "JAX001", "JAX002", "BND001"} <= triaged


# -- boundaries.toml vs the real import graph --------------------------------

def _scan_imports(rel, tree):
    """Import extraction written independently of the checker's
    bnd.extract_imports — a bug there must not blind this cross-check."""
    mod = rel[:-3].replace("/", ".")
    is_pkg = mod.endswith(".__init__")
    if is_pkg:
        mod = mod[: -len(".__init__")]
    pkg_parts = mod.split(".") if is_pkg else mod.split(".")[:-1]
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names
                       if a.name.split(".")[0] == "tpu9")
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                base = node.module or ""
            else:
                anchor = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                base = ".".join(anchor + ([node.module] if node.module
                                          else []))
            if base.split(".")[0] == "tpu9":
                for a in node.names:
                    if a.name != "*":
                        out.add(f"{base}.{a.name}")
                if not node.names:
                    out.add(base)
    return out


def _real_imports():
    """Independent import scan (not the checker's walker): module ->
    set of imported tpu9 targets."""
    edges = {}
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, "tpu9")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
            rel = rel.replace(os.sep, "/")
            with open(os.path.join(REPO, rel)) as f:
                tree = ast.parse(f.read())
            mod = rel[:-3].replace("/", ".")
            if mod.endswith(".__init__"):
                mod = mod[: -len(".__init__")]
            edges.setdefault(mod, set()).update(_scan_imports(rel, tree))
    return edges


def test_independent_scanner_agrees_with_checker_extraction():
    """The two extractors (checker's + this test's) must agree on the real
    tree — divergence means one of them mis-resolves an import form."""
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, "tpu9")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
            rel = rel.replace(os.sep, "/")
            with open(os.path.join(REPO, rel)) as f:
                tree = ast.parse(f.read())
            checker = {t for t, _ in bnd.extract_imports(rel, tree)}
            ours = _scan_imports(rel, tree)
            assert checker == ours, f"extractors disagree on {rel}"


def test_boundaries_toml_matches_real_import_graph():
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    edges = _real_imports()

    def covered(target, allowed, selfpkg):
        return (target == selfpkg or target.startswith(selfpkg + ".")
                or any(target == a or target.startswith(a + ".")
                       for a in allowed))

    # 1) every [allow] contract holds against reality. Most specific key
    # wins, mirroring the checker: a module governed by a deeper allow
    # key (tpu9.serving.shard under tpu9.serving — the ONE serving
    # subtree allowed to reach tpu9.parallel) answers to that contract
    # alone, not to every enclosing one.
    for pkg, allowed in cfg.allow.items():
        for mod, targets in edges.items():
            if not (mod == pkg or mod.startswith(pkg + ".")):
                continue
            if any(k != pkg and len(k) > len(pkg)
                   and (mod == k or mod.startswith(k + "."))
                   for k in cfg.allow):
                continue
            for t in targets:
                assert covered(t, allowed, pkg), \
                    f"{mod} imports {t}, outside {pkg}'s allow contract"
    # 2) the forbid edges the engine split depends on are really absent
    for pkg, banned in cfg.forbid.items():
        for mod, targets in edges.items():
            if not (mod == pkg or mod.startswith(pkg + ".")):
                continue
            for t in targets:
                for b in banned:
                    assert not (t == b or t.startswith(b + ".")), \
                        f"{mod} imports {t}, forbidden by {pkg} -> {b}"
    # 3) restricted modules are touched only by their declared importers
    for rmod, importers in cfg.restricted.items():
        for mod, targets in edges.items():
            for t in targets:
                if t == rmod or t.startswith(rmod + "."):
                    assert any(mod == i or mod.startswith(i + ".")
                               for i in importers), \
                        f"{mod} touches restricted {rmod}"
    # 4) the contracts are live: the strong-form packages exist and import
    #    something (an allow entry for a dead package would be vacuous)
    for pkg in ("tpu9.serving", "tpu9.router", "tpu9.ops"):
        assert any(m == pkg or m.startswith(pkg + ".") for m in edges)


def test_slo_observability_contracts_declared_and_live():
    """ISSUE 12 satellite: the fleet SLO/timeline modules carry explicit
    boundary contracts — observability is a closed leaf (no reverse edge
    into serving/router/gateway), and the slo/timeline modules are
    restricted to the control plane + CLI. The cross-check test
    above asserts these against the real import graph; this one asserts
    they are DECLARED (a deleted contract must fail loudly, not vacuously
    pass) and still live."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    assert "tpu9.observability" in cfg.allow
    # the leaf must not be allowed to reach the planes that record into it
    for banned in ("tpu9.serving", "tpu9.router", "tpu9.gateway",
                   "tpu9.worker"):
        assert banned not in cfg.allow["tpu9.observability"]
    for rmod in ("tpu9.observability.timeline", "tpu9.observability.slo"):
        assert rmod in cfg.restricted, rmod
        importers = cfg.restricted[rmod]
        assert "tpu9.gateway" in importers and "tpu9.cli" in importers
        # serving must NOT grow a reverse edge into the fleet ledger
        assert not any(i == "tpu9.serving" or i.startswith("tpu9.serving.")
                       for i in importers)
    # liveness: the gateway really imports both restricted modules (via
    # fleetobs), so the contracts guard a real edge, not a dead name
    edges = _real_imports()
    gw = edges.get("tpu9.gateway.fleetobs", set())
    assert any(t.startswith("tpu9.observability.timeline") for t in gw)
    assert any(t.startswith("tpu9.observability.slo") for t in gw)


def test_health_plane_contract_declared_and_live():
    """ISSUE 14 satellite: the replica health plane is a closed leaf —
    the watchdog/black-box module is restricted to the runner (watchdog
    on the heartbeat loop), the gateway (verdict fold + black-box store),
    and the CLI; the serving engine and the router must NOT import
    it (they exchange plain scalars over the heartbeat). Declared here,
    asserted against the real import graph by the cross-check test."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    rmod = "tpu9.observability.health"
    assert rmod in cfg.restricted
    importers = cfg.restricted[rmod]
    for needed in ("tpu9.gateway", "tpu9.runner", "tpu9.worker",
                   "tpu9.cli"):
        assert needed in importers, importers
    # NO reverse edge into the planes the watchdog judges
    for banned in ("tpu9.serving", "tpu9.router"):
        assert not any(i == banned or i.startswith(banned + ".")
                       for i in importers), importers
    # liveness: the runner (watchdog + post-mortem ship) and the gateway
    # (gauge publication + black-box clamp) really import the module —
    # the contract guards real edges, not a dead name
    edges = _real_imports()
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.runner.llm", set()))
    gw_edges = (edges.get("tpu9.gateway.fleetobs", set())
                | edges.get("tpu9.gateway.gateway", set()))
    assert any(t.startswith(rmod) for t in gw_edges)
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.worker.lifecycle", set()))
    # and the serving/router planes genuinely do not
    for mod, targets in edges.items():
        if mod.startswith("tpu9.serving") or mod.startswith("tpu9.router"):
            assert not any(t.startswith(rmod) for t in targets), mod


def test_fault_plane_contract_declared_and_live():
    """ISSUE 15 satellite: the fault-injection plane is chaos tooling —
    restricted to its declared hook sites (runner/worker/cache, all
    env-gated lazy imports) and the test plane. The gateway/
    router/serving planes must never import it: the recovery machinery
    under test cannot depend on the failure injector."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    rmod = "tpu9.testing.faults"
    assert rmod in cfg.restricted
    importers = cfg.restricted[rmod]
    for needed in ("tpu9.runner", "tpu9.worker", "tpu9.cache",
                   "tpu9.testing"):
        assert needed in importers, importers
    for banned in ("tpu9.gateway", "tpu9.router", "tpu9.serving"):
        assert not any(i == banned or i.startswith(banned + ".")
                       for i in importers), importers
    # liveness: the declared hook sites really import it (lazily)
    edges = _real_imports()
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.runner.llm", set()))
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.cache.client", set()))
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.worker.worker", set()))
    # and the production planes genuinely do not
    for mod, targets in edges.items():
        if (mod.startswith("tpu9.gateway") or mod.startswith("tpu9.router")
                or mod.startswith("tpu9.serving")):
            assert not any(t.startswith(rmod) for t in targets), mod
    # the hook-site imports are env-GATED: a production container without
    # TPU9_FAULTS never executes them (source-level check on the gate —
    # the raw environ read now lives in config.env_faults_spec, ISSUE 18)
    for rel in ("tpu9/runner/llm.py", "tpu9/cache/client.py",
                "tpu9/worker/worker.py"):
        src = open(os.path.join(REPO, rel)).read()
        gate = src.index("if env_faults_spec()")
        imp = src.index("from ..testing.faults import")
        assert gate < imp, f"{rel}: faults import is not env-gated"
    cfg_src = open(os.path.join(REPO, "tpu9", "config.py")).read()
    assert 'os.environ.get("TPU9_FAULTS"' in cfg_src


def test_kvwire_contract_declared_and_live():
    """ISSUE 16 satellite: the KV wire format is a serialization boundary
    — restricted to the two ends of the pipe (serving encodes/decodes,
    the runner moves payloads between transport and engine) and the
    cache transport. The gateway and router must NEVER import it: they
    speak keys/flags/token counts, and a payload crossing the
    control plane is exactly the layering bug this contract catches."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    rmod = "tpu9.serving.kvwire"
    assert rmod in cfg.restricted
    importers = cfg.restricted[rmod]
    for needed in ("tpu9.serving", "tpu9.runner", "tpu9.cache"):
        assert needed in importers, importers
    for banned in ("tpu9.gateway", "tpu9.router"):
        assert not any(i == banned or i.startswith(banned + ".")
                       for i in importers), importers
    # liveness: the pool (encode/decode) and the runner (header peeks on
    # publish/drain) really import the module — real edges, not a name
    edges = _real_imports()
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.serving.kvpool", set()))
    assert any(t.startswith(rmod)
               for t in edges.get("tpu9.runner.llm", set()))
    # and the control plane genuinely does not touch payloads
    for mod, targets in edges.items():
        if mod.startswith("tpu9.gateway") or mod.startswith("tpu9.router"):
            assert not any(t.startswith(rmod) for t in targets), mod
    # the module runs on the replica: the hot-path policy must cover it
    raw = tomlmini.load_file(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    assert "tpu9/serving/kvwire.py" in raw["jax"]["hotpath"]["files"]


def test_scaleout_contract_declared_and_live():
    """ISSUE 17 satellite: the scale-out plane is a closed subsystem —
    an [allow] contract caps its import surface (cache/observability/
    config/utils; never serving, router, gateway or worker: the planes
    CALL it, it calls nobody back), and a [restricted] list names its
    only importers (gateway coordinator host, abstractions predictive
    wrapper, CLI tree-hint bootstrap). Declared here, asserted
    against the real import graph by the cross-check test above."""
    cfg = bnd.BoundaryConfig.load(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    assert "tpu9.scaleout" in cfg.allow
    for banned in ("tpu9.serving", "tpu9.router", "tpu9.gateway",
                   "tpu9.worker", "tpu9.abstractions"):
        assert banned not in cfg.allow["tpu9.scaleout"]
    assert "tpu9.scaleout" in cfg.restricted
    importers = cfg.restricted["tpu9.scaleout"]
    for needed in ("tpu9.gateway", "tpu9.abstractions", "tpu9.cli"):
        assert needed in importers, importers
    # the serving engine ships flat scaleout_* scalars over the
    # heartbeat and the router parses plain stats — no import edge
    for banned in ("tpu9.serving", "tpu9.router"):
        assert not any(i == banned or i.startswith(banned + ".")
                       for i in importers), importers
    # serving's loud forbid list names the reverse edge explicitly
    assert "tpu9.scaleout" in cfg.forbid["tpu9.serving"]
    # liveness: the declared importers really import it — the gateway
    # (coordinator + report), fleetobs (ledger feed + plan publish) and
    # the abstractions endpoint (predictive policy wrap)
    edges = _real_imports()
    gw_edges = (edges.get("tpu9.gateway.gateway", set())
                | edges.get("tpu9.gateway.fleetobs", set()))
    assert any(t.startswith("tpu9.scaleout") for t in gw_edges)
    assert any(t.startswith("tpu9.scaleout")
               for t in edges.get("tpu9.abstractions.endpoint", set()))
    # and the serving/router planes genuinely do not
    for mod, targets in edges.items():
        if mod.startswith("tpu9.serving") or mod.startswith("tpu9.router"):
            assert not any(t.startswith("tpu9.scaleout")
                           for t in targets), mod


def test_tomlmini_parses_boundaries_toml():
    raw = tomlmini.load_file(
        os.path.join(REPO, "tpu9", "analysis", "boundaries.toml"))
    assert "tpu9.serving" in raw["allow"]
    assert raw["jax"]["hotpath"]["roots"] == ["_serve_loop",
                                              "_serve_loop_inner"]
    assert "tpu9/serving/engine.py" in raw["jax"]["hotpath"]["files"]
