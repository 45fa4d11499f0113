"""tpu9 CLI.

Reference analogue: the ``beta9`` click CLI (``sdk/src/beta9/cli/``, 21
modules: deploy/serve/run/task/container/machine/pool/worker/volume/secret/
token/config/shell/...). Same command surface, tpu9 semantics.

Server commands (the reference ships separate gateway/worker binaries;
tpu9's single wheel serves both):

    tpu9 gateway --config cluster.yaml
    tpu9 worker  --gateway-state 10.0.0.1:14950 --tpu v5e-8
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import click

from ..config import load_config
from ..sdk.client import Context, GatewayClient
from ..utils.aio import spawn as aio_spawn


def _client() -> GatewayClient:
    return GatewayClient()


@click.group()
def cli() -> None:
    """tpu9 — TPU-native serverless AI runtime."""


# ---------------------------------------------------------------------------
# config / auth
# ---------------------------------------------------------------------------

@cli.group()
def config() -> None:
    """Manage gateway contexts."""


@config.command("set")
@click.option("--name", default="default")
@click.option("--gateway-url", required=True)
@click.option("--token", required=True)
def config_set(name: str, gateway_url: str, token: str) -> None:
    ctx = Context(gateway_url=gateway_url, token=token, name=name)
    ctx.save()
    click.echo(f"context {name!r} saved")


@config.command("show")
def config_show() -> None:
    ctx = Context.load()
    click.echo(json.dumps({"name": ctx.name, "gateway_url": ctx.gateway_url,
                           "token": ctx.token[:8] + "..."}, indent=2))


@cli.command()
def whoami() -> None:
    """Check auth against the gateway."""
    click.echo(json.dumps(_client().auth_check(), indent=2))


# ---------------------------------------------------------------------------
# deploy / invoke
# ---------------------------------------------------------------------------

@cli.command()
@click.argument("target")          # module.py:object
@click.option("--name", default="")
def deploy(target: str, name: str) -> None:
    """Deploy a decorated object: ``tpu9 deploy app.py:handler``."""
    obj = _load_target(target)
    out = obj.deploy(name or obj.name or target.split(":")[-1])
    click.echo(json.dumps(out, indent=2))


@cli.command()
@click.argument("target")
@click.option("--name", default="dev")
@click.option("--watch/--no-watch", default=True)
def serve(target: str, name: str, watch: bool) -> None:
    """Hot-reload dev loop (reference ``beta9 serve``): start an ephemeral
    serve session, tail its container logs, re-sync on source change. Uses
    /rpc/deploy for /endpoint/<name> routability; the session deactivates
    its deployment rows on exit, and it survives broken edits."""
    import time as _time

    from ..sdk.sync import _ignored

    client = _client()

    def snapshot(root: str = ".") -> dict:
        # watch exactly what build_archive would sync (sync.py ignore rules)
        out = {}
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if not _ignored(d)]
            for fn in filenames:
                if _ignored(fn):
                    continue
                p = os.path.join(dirpath, fn)
                try:
                    out[p] = os.path.getmtime(p)
                except OSError:
                    pass
        return out

    session_deployments: list[str] = []

    def do_serve():
        obj = _load_target(target)
        stub_id = obj.prepare_runtime(force=True)
        # a deployment row gives /endpoint/<name> routability; the session
        # deactivates its rows on exit so dev churn doesn't accumulate
        out = client.deploy(stub_id, name)
        session_deployments.append(out["deployment_id"])
        click.echo(f"→ serving {name} v{out['version']} at "
                   f"{out['invoke_url']}")
        return obj, stub_id

    mtimes = snapshot()
    obj, stub_id = do_serve()
    seen_logs: dict[str, str] = {}
    last_error = ""
    click.echo("watching for changes (Ctrl-C to stop)...")
    try:
        while True:
            _time.sleep(1.0)
            # tail logs of this stub's containers (incremental via since=)
            try:
                containers = client._run(lambda c: c.request(
                    "GET", "/api/v1/container"))
                for ct in containers:
                    if ct.get("stub_id") != stub_id:
                        continue
                    cid = ct["container_id"]
                    since = seen_logs.get(cid, "0")
                    logs = client._run(lambda c: c.request(
                        "GET", f"/api/v1/container/{cid}/logs?since={since}"))
                    for entry in logs:
                        click.echo(f"[{cid[:10]}] {entry['line']}")
                        seen_logs[cid] = entry["id"]
                last_error = ""
            except Exception as exc:
                msg = f"{type(exc).__name__}: {exc}"
                if msg != last_error:   # surface once, don't spam
                    click.echo(f"[serve] log tail failing: {msg}")
                    last_error = msg
            if watch:
                now = snapshot()
                if now != mtimes:
                    mtimes = now        # baseline BEFORE deploying so edits
                    click.echo("… change detected, reloading")
                    try:                # during deploy retrigger next tick
                        obj, stub_id = do_serve()
                    except Exception as exc:
                        # broken edit or transient gateway error: keep
                        # watching; the next save retries
                        click.echo(f"[serve] reload failed: "
                                   f"{type(exc).__name__}: {exc}")
    except KeyboardInterrupt:
        click.echo("\nserve loop stopped; cleaning up session deployments")
        for dep_id in session_deployments:
            try:
                client._run(lambda c: c.request(
                    "DELETE", f"/api/v1/deployment/{dep_id}"))
            except Exception:
                pass


@cli.command()
@click.argument("name")
@click.argument("payload", default="{}")
@click.option("--stream", is_flag=True,
              help="relay SSE events as they arrive (LLM token streams)")
def invoke(name: str, payload: str, stream: bool) -> None:
    """Invoke a deployment: ``tpu9 invoke my-endpoint '{"x": 1}'``."""
    if stream:
        import asyncio as _asyncio

        from ..sdk.client import AsyncGatewayClient

        async def run() -> None:
            client = AsyncGatewayClient()
            try:
                async for event in client.invoke_stream(
                        name, json.loads(payload)):
                    click.echo(json.dumps(event))
            finally:
                await client.close()

        _asyncio.run(run())
        return
    click.echo(json.dumps(_client().invoke(name, json.loads(payload)),
                          indent=2))


def _load_target(target: str):
    path, _, attr = target.partition(":")
    if not attr:
        raise click.UsageError("target must be path.py:object")
    import importlib.util
    # module name must match what the runner will import from the synced
    # workspace (handler_spec is derived from it)
    mod_name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return getattr(module, attr)


# ---------------------------------------------------------------------------
# resources
# ---------------------------------------------------------------------------

@cli.group()
def task() -> None:
    """Inspect and manage tasks."""


@task.command("list")
def task_list() -> None:
    out = _client()._run(lambda c: c.request("GET", "/api/v1/task"))
    click.echo(json.dumps(out, indent=2))


@task.command("status")
@click.argument("task_id")
def task_status(task_id: str) -> None:
    click.echo(json.dumps(_client().task_status(task_id), indent=2))


@task.command("result")
@click.argument("task_id")
@click.option("--timeout", default=0.0)
def task_result(task_id: str, timeout: float) -> None:
    click.echo(json.dumps(_client().task_result(task_id, timeout), indent=2))


@task.command("cancel")
@click.argument("task_id")
def task_cancel(task_id: str) -> None:
    click.echo(json.dumps({"ok": _client().task_cancel(task_id)}))


@cli.command()
@click.argument("container_id")
@click.option("--cmd", default="", help="command instead of a shell")
def shell(container_id: str, cmd: str) -> None:
    """Interactive shell into a running container (shell/shell.go:53
    analogue over the gateway websocket instead of dropbear+TCP tunnel).
    Works with a real TTY (raw mode) or piped stdin for scripted use."""
    import base64
    import sys

    import aiohttp

    ctx = Context.load()
    url = (ctx.gateway_url.rstrip("/")
           + f"/api/v1/container/{container_id}/shell")

    interactive = sys.stdin.isatty() and not cmd

    async def run() -> int:
        exit_code = 0
        # scripted modes (piped/redirected stdin or --cmd) run one-shot
        # under the PTY: deterministic exit code, no prompt noise, no
        # readline EOF timing games
        script = cmd
        if not interactive and not script:
            script = sys.stdin.read()
        async with aiohttp.ClientSession(headers={
                "Authorization": f"Bearer {ctx.token}"}) as session:
            async with session.ws_connect(url) as ws:
                loop = asyncio.get_running_loop()
                restore = None
                reader_installed = False

                def on_stdin() -> None:
                    data = os.read(sys.stdin.fileno(), 65536)
                    if not data:
                        loop.remove_reader(sys.stdin.fileno())
                        data = b"\x04"   # PTY EOF: Ctrl-D
                    # spawn (ASY002): a GC'd send task would eat typed
                    # keystrokes; ws.send_json serializes internally
                    aio_spawn(ws.send_json(
                        {"d": base64.b64encode(data).decode()}),
                        name="shell-stdin")

                try:
                    if interactive:
                        import termios
                        import tty
                        restore = termios.tcgetattr(sys.stdin.fileno())
                        tty.setraw(sys.stdin.fileno())
                        sz = os.get_terminal_size()
                        await ws.send_json(
                            {"resize": [sz.lines, sz.columns]})
                        loop.add_reader(sys.stdin.fileno(), on_stdin)
                        reader_installed = True
                    else:
                        await ws.send_json(
                            {"cmd": ["/bin/sh", "-c", script]})

                    async for msg in ws:
                        if msg.type != aiohttp.WSMsgType.TEXT:
                            break
                        entry = json.loads(msg.data)
                        if entry.get("d"):
                            sys.stdout.buffer.write(
                                base64.b64decode(entry["d"]))
                            sys.stdout.buffer.flush()
                        if entry.get("error"):
                            print(f"shell error: {entry['error']}",
                                  file=sys.stderr)
                        if "exit" in entry:
                            exit_code = int(entry["exit"])
                            break
                finally:
                    if reader_installed:
                        try:
                            loop.remove_reader(sys.stdin.fileno())
                        except (OSError, ValueError):
                            pass
                    if restore is not None:
                        import termios
                        termios.tcsetattr(sys.stdin.fileno(),
                                          termios.TCSADRAIN, restore)
        return exit_code

    raise SystemExit(asyncio.run(run()))


@cli.group()
def container() -> None:
    """Inspect and manage containers."""


@container.command("list")
def container_list() -> None:
    out = _client()._run(lambda c: c.request("GET", "/api/v1/container"))
    click.echo(json.dumps(out, indent=2))


@container.command("stop")
@click.argument("container_id")
def container_stop(container_id: str) -> None:
    out = _client()._run(lambda c: c.request(
        "POST", f"/api/v1/container/{container_id}/stop", json_body={}))
    click.echo(json.dumps(out))


@container.command("logs")
@click.argument("container_id")
def container_logs(container_id: str) -> None:
    out = _client()._run(lambda c: c.request(
        "GET", f"/api/v1/container/{container_id}/logs"))
    for entry in out:
        click.echo(f"[{entry.get('stream','')}] {entry.get('line','')}")


@cli.command("workers")
def workers_list() -> None:
    out = _client()._run(lambda c: c.request("GET", "/api/v1/worker"))
    click.echo(json.dumps(out, indent=2))


@cli.command("pools")
def pools_status() -> None:
    out = _client()._run(lambda c: c.request("GET", "/api/v1/pools"))
    click.echo(json.dumps(out, indent=2))


@cli.command("deployments")
def deployments_list() -> None:
    out = _client()._run(lambda c: c.request("GET", "/api/v1/deployment"))
    click.echo(json.dumps(out, indent=2))


@cli.command("stubs")
def stubs_list() -> None:
    """List workspace stubs (all registered functions/endpoints)."""
    out = _client()._run(lambda c: c.request("GET", "/api/v1/stub"))
    click.echo(json.dumps(out, indent=2))


@cli.group()
def machine() -> None:
    """BYOC machine fleet (reference pkg/agent + machine API)."""


@machine.command("create")
@click.argument("name")
@click.option("--pool", default="default")
@click.option("--max-workers", default=1)
def machine_create(name: str, pool: str, max_workers: int) -> None:
    """Register a machine; prints its ONE-TIME join token."""
    out = _client().request("POST", "/api/v1/machine",
                            json_body={"name": name, "pool": pool,
                                       "max_workers": max_workers})
    click.echo(json.dumps(out, indent=2))
    click.echo(f"\nOn the machine, run:\n  tpu9 agent join "
               f"--gateway-url <url> --token {out['join_token']}", err=True)


@machine.command("list")
@click.option("--pool", default="")
def machine_list(pool: str) -> None:
    q = f"?pool={pool}" if pool else ""
    out = _client().request("GET", f"/api/v1/machine{q}")
    click.echo(json.dumps(out, indent=2))


@machine.command("delete")
@click.argument("machine_id")
def machine_delete(machine_id: str) -> None:
    out = _client().request("DELETE", f"/api/v1/machine/{machine_id}")
    click.echo(json.dumps(out))


@machine.command("logs")
@click.argument("machine_id")
@click.option("--tail", default=200, help="lines from the end")
def machine_logs(machine_id: str, tail: int) -> None:
    """Worker logs relayed through the machine's agent."""
    out = _client().request(
        "GET", f"/api/v1/machine/{machine_id}/logs?tail={tail}")
    for line in out.get("lines", []):
        click.echo(line)


@cli.group()
def agent() -> None:
    """Machine-owner agent (runs ON the BYOC machine)."""


@agent.command("join")
@click.option("--gateway-url", required=True)
@click.option("--token", "join_token", required=True,
              help="one-time join token from `tpu9 machine create`")
@click.option("--poll-interval", default=2.0)
@click.option("--worker-arg", "worker_args", multiple=True,
              help="extra args passed to spawned workers "
                   "(e.g. --worker-arg=--runtime=native)")
@click.option("--skip-preflight", is_flag=True,
              help="join even if preflight checks fail (debugging)")
def agent_join(gateway_url: str, join_token: str, poll_interval: float,
               worker_args: tuple[str, ...], skip_preflight: bool) -> None:
    """Join the gateway and reconcile local workers forever."""
    from ..agent import Agent

    async def main() -> None:
        ag = Agent(gateway_url, join_token,
                   poll_interval_s=poll_interval,
                   worker_args=list(worker_args),
                   skip_preflight=skip_preflight)
        await ag.start()
        click.echo(f"machine {ag.machine_id} joined pool {ag.pool} "
                   f"(max_workers={ag.max_workers})")
        try:
            await asyncio.Event().wait()
        finally:
            await ag.stop()

    asyncio.run(main())


@cli.group()
def secret() -> None:
    """Workspace secrets."""


@secret.command("set")
@click.argument("name")
@click.argument("value")
def secret_set(name: str, value: str) -> None:
    _client()._run(lambda c: c.request("POST", "/api/v1/secret",
                                       json_body={"name": name,
                                                  "value": value}))
    click.echo("ok")


@secret.command("list")
def secret_list() -> None:
    click.echo(json.dumps(
        _client()._run(lambda c: c.request("GET", "/api/v1/secret"))))


@secret.command("delete")
@click.argument("name")
def secret_delete(name: str) -> None:
    _client()._run(lambda c: c.request("DELETE", f"/api/v1/secret/{name}"))
    click.echo("ok")


@cli.group()
def volume() -> None:
    """Workspace volumes."""


@volume.command("list")
def volume_list() -> None:
    click.echo(json.dumps(
        _client()._run(lambda c: c.request("GET", "/api/v1/volume")),
        indent=2))


@volume.command("create")
@click.argument("name")
def volume_create(name: str) -> None:
    out = _client()._run(
        lambda c: c.request("POST", f"/api/v1/volume/{name}"))
    click.echo(json.dumps(out, indent=2))


@volume.command("rm")
@click.argument("name")
def volume_rm(name: str) -> None:
    out = _client()._run(
        lambda c: c.request("DELETE", f"/api/v1/volume/{name}"))
    click.echo(json.dumps(out, indent=2))


@volume.command("ls")
@click.argument("name")
def volume_ls(name: str) -> None:
    from ..sdk.primitives import Volume
    click.echo(json.dumps(Volume(name).ls(), indent=2))


@volume.command("upload")
@click.argument("name")
@click.argument("local_path")
@click.option("--remote", default="")
def volume_upload(name: str, local_path: str, remote: str) -> None:
    from ..sdk.primitives import Volume
    n = Volume(name).upload(local_path, remote)
    click.echo(f"uploaded {n} bytes")


@volume.command("download")
@click.argument("name")
@click.argument("remote_path")
@click.argument("local_path")
def volume_download(name: str, remote_path: str, local_path: str) -> None:
    from ..sdk.primitives import Volume
    data = Volume(name).download(remote_path)
    with open(local_path, "wb") as f:
        f.write(data)
    click.echo(f"wrote {len(data)} bytes to {local_path}")


@cli.group()
def image() -> None:
    """Container images."""


@image.command("build")
@click.option("--packages", "-p", multiple=True)
@click.option("--command", "-c", "commands", multiple=True)
def image_build(packages, commands) -> None:
    from ..sdk.image import Image
    img = Image().add_python_packages(list(packages)).add_commands(
        list(commands))
    image_id = img.ensure_built(_client())
    click.echo(image_id)


@cli.command("startup-report")
def startup_report() -> None:
    """Cold-start phase latency report across the fleet (reference
    benchmarks/sandbox_startup_report.py): p50/p95/max per lifecycle phase."""
    data = _client()._run(lambda c: c.request("GET", "/api/v1/metrics"))
    rows: dict[str, dict] = {}
    # embedded-worker topologies share one registry: the gateway's top-level
    # view already contains the shipped worker snapshots — don't double-count
    worker_ids = set(data.get("workers", {}).keys())
    top_gauges = data.get("gauges", {})
    embedded = any(f'worker="{wid}"' in g for wid in worker_ids
                   for g in top_gauges)
    sources = list(data.get("workers", {}).values())
    if not embedded:
        sources.append(data)
    for src in sources:
        for key, snap in src.get("summaries", {}).items():
            if "tpu9_startup_phase_s" not in key:
                continue
            phase = key.split('phase="')[-1].rstrip('"}')
            cur = rows.setdefault(phase, {"count": 0, "p50": 0.0,
                                          "p95": 0.0, "max": 0.0})
            cur["count"] += snap["count"]
            cur["p50"] = max(cur["p50"], snap["p50"])
            cur["p95"] = max(cur["p95"], snap["p95"])
            cur["max"] = max(cur["max"], snap["max"])
    if not rows:
        click.echo("no startup phases recorded yet")
        return
    click.echo(f"{'phase':<28}{'count':>7}{'p50':>10}{'p95':>10}{'max':>10}")
    for phase, r in sorted(rows.items(), key=lambda kv: kv[1]['p50']):
        click.echo(f"{phase:<28}{r['count']:>7}{r['p50']*1000:>9.1f}ms"
                   f"{r['p95']*1000:>9.1f}ms{r['max']*1000:>9.1f}ms")


@cli.command("bench-suite")
@click.argument("suite", type=click.Choice(["load", "cache", "startup",
                                            "full"]))
@click.option("--out-dir", default="", help="run directory (default "
              "benchruns/<timestamp>-<suite>)")
@click.option("--quick", is_flag=True, help="small stages for smoke runs")
def bench_suite(suite: str, out_dir: str, quick: bool) -> None:
    """Structured load/cache/startup benchmarks with anti-fooling validators
    (reference benchmarks/b9bench): every headline number carries
    machine-checked SHA/cache-path/backoff evidence; a metric whose proof is
    missing FAILS the run. Writes metrics.jsonl + summary.json + summary.md."""
    from ..benchsuite.runner import run_suite
    summary = run_suite(suite, out_dir=out_dir or None, quick=quick)
    click.echo(json.dumps({k: v for k, v in summary.items()
                           if k != "metrics"}, indent=2))
    if not summary["passed"]:
        raise SystemExit(1)


@cli.command("usage")
@click.option("--hours", default=24)
def usage_cmd(hours: int) -> None:
    """Metered usage for this workspace: container-seconds, chip-seconds,
    requests per hourly bucket (reference usage_openmeter.go meters)."""
    data = _client()._run(lambda c: c.request(
        "GET", f"/api/v1/usage?hours={hours}"))
    click.echo(f"{'bucket':<16}" + "".join(
        f"{m:>20}" for m in ("container_seconds", "chip_seconds",
                             "requests")))
    for bucket, row in data.get("buckets", {}).items():
        click.echo(f"{bucket:<16}" + "".join(
            f"{row.get(m, 0):>20.1f}" for m in ("container_seconds",
                                                "chip_seconds", "requests")))
    totals = data.get("totals", {})
    click.echo("totals: " + json.dumps(totals))


@cli.command("traces")
@click.option("--trace-id", default="")
@click.option("--limit", default=100)
def traces_cmd(trace_id: str, limit: int) -> None:
    """Fleet trace spans (gateway → router → engine, worker cold starts)."""
    q = f"?limit={limit}" + (f"&trace_id={trace_id}" if trace_id else "")
    data = _client()._run(lambda c: c.request("GET", f"/api/v1/traces{q}"))
    for sp in data.get("spans", []):
        indent = "  " if sp.get("parentSpanId") else ""
        click.echo(f"{indent}{sp['traceId'][:8]} {sp['name']:<24} "
                   f"{sp['durationMs']:>9.2f}ms  {sp.get('status','')}")


def _fmt_decision(rec: dict) -> str:
    """One ledger record, one line: plane decision → chosen, then the
    rejected alternatives (!alt(reason)) and the input signals."""
    rej_txt = " ".join(f"!{r.get('alternative', '')}({r.get('reason', '')})"
                       for r in rec.get("rejected") or [])
    sig = rec.get("signals") or {}
    sig_txt = " ".join(f"{k}={v}" for k, v in list(sig.items())[:8])
    body = (f"{rec.get('plane', ''):<11}{rec.get('decision', ''):<14}"
            f"-> {rec.get('chosen', '') or '-'}")
    if rej_txt:
        body += f"  {rej_txt}"
    if sig_txt:
        body += f"  [{sig_txt}]"
    return body


@cli.command("decisions")
@click.option("--plane", default="",
              help="admission|placement|failover|migration|autoscaler")
@click.option("--request-id", default="", help="one request's chain")
@click.option("--since", default=0.0, type=float, help="wall-clock floor")
@click.option("--limit", default=50)
@click.option("--json", "as_json", is_flag=True, help="raw records")
def decisions_cmd(plane: str, request_id: str, since: float, limit: int,
                  as_json: bool) -> None:
    """Fleet decision ledger (ISSUE 19): WHY the control planes chose
    what they chose — shed verdicts, placement orders, failover resume
    modes, drain exports, autoscaler ticks — each with the rejected
    alternatives and the input signals behind the choice."""
    q = f"?limit={limit}&since={since}"
    if plane:
        q += f"&plane={plane}"
    if request_id:
        q += f"&request_id={request_id}"
    data = _client()._run(
        lambda c: c.request("GET", f"/api/v1/decisions{q}"))
    records = data.get("records", [])
    if as_json:
        click.echo(json.dumps(records, indent=2))
        return
    if not records:
        click.echo("no decision records (yet)")
        return
    for rec in records:
        stamp = time.strftime("%H:%M:%S",
                              time.localtime(float(rec.get("ts", 0.0))))
        click.echo(f"{stamp} {_fmt_decision(rec)}")


@cli.command("why")
@click.argument("request_id")
@click.option("--json", "as_json", is_flag=True, help="raw chain + spans")
def why_cmd(request_id: str, as_json: bool) -> None:
    """The full story of one request: its decision chain (admission →
    placement → failover → migration) interleaved with the trace span
    tree. `tpu9 traces` says what happened; this says why."""
    client = _client()
    ddata = client._run(lambda c: c.request(
        "GET", f"/api/v1/decisions?request_id={request_id}&limit=500"))
    tdata = client._run(lambda c: c.request(
        "GET", f"/api/v1/traces?trace_id={request_id}&limit=1000"))
    records = ddata.get("records", [])
    spans = tdata.get("spans", [])
    if as_json:
        click.echo(json.dumps({"records": records, "spans": spans},
                              indent=2))
        return
    # merge on the wall clock; a decision made inside a span sorts after
    # the span's start, which reads as cause-then-effect
    events = [(sp.get("startTimeUnixNano", 0) / 1e9, 0, sp)
              for sp in spans]
    events += [(float(rec.get("ts", 0.0)), 1, rec) for rec in records]
    if not events:
        click.echo(f"no evidence for request {request_id} "
                   "(expired, or never traced?)")
        return
    events.sort(key=lambda e: (e[0], e[1]))
    t0 = events[0][0]
    for ts, kind, item in events:
        if kind == 0:
            indent = "  " if item.get("parentSpanId") else ""
            click.echo(f"+{ts - t0:8.3f}s  span       "
                       f"{indent}{item.get('name', ''):<24}"
                       f"{item.get('durationMs', 0.0):>9.2f}ms  "
                       f"{item.get('status', '')}")
        else:
            click.echo(f"+{ts - t0:8.3f}s  {_fmt_decision(item)}")


@cli.command("flight")
@click.argument("stub_id")
@click.option("--container-id", default="", help="pin one replica")
@click.option("--limit", default=64)
@click.option("--since-seq", default=0,
              help="only records newer than this seq (incremental poll)")
def flight_cmd(stub_id: str, container_id: str, limit: int,
               since_seq: int) -> None:
    """Engine flight-recorder tail: per-window batch composition, K picks,
    spec accept/rollback, KV churn — the serve loop's black box."""
    q = f"?stub_id={stub_id}&limit={limit}&since_seq={since_seq}"
    if container_id:
        q += f"&container_id={container_id}"
    data = _client()._run(lambda c: c.request("GET", f"/api/v1/flight{q}"))
    for rec in data.get("flight", []):
        base = (f"#{rec['seq']:<6} {rec['kind']:<8}")
        if rec["kind"] in ("decode", "verify"):
            base += (f" k={rec.get('k', 0):<3} pick={rec.get('pick', ''):<10}"
                     f" batch={rec.get('batch', 0)}"
                     f" wait={rec.get('wait_s', 0) * 1000:7.2f}ms"
                     f" host={rec.get('host_s', 0) * 1000:6.2f}ms")
            if rec["kind"] == "verify":
                base += (f" spec={rec.get('spec_accepted', 0)}"
                         f"/{rec.get('spec_proposed', 0)}")
        elif rec["kind"] == "admit":
            base += (f" req={rec.get('request_id', '')}"
                     f" prompt={rec.get('prompt_tokens', 0)}"
                     f" cached={rec.get('cached_tokens', 0)}"
                     f" dur={rec.get('dur_s', 0) * 1000:7.2f}ms")
        else:
            base += f" {json.dumps({k: v for k, v in rec.items() if k not in ('seq', 'kind', 'ts')})}"
        click.echo(base)


@cli.command("coldstart")
@click.option("--stub-id", default="", help="filter one deployment")
@click.option("--container-id", default="", help="pin one replica")
@click.option("--json", "as_json", is_flag=True, help="raw records")
def coldstart_cmd(stub_id: str, container_id: str, as_json: bool) -> None:
    """Per-replica cold-start decomposition: plan→fetch→put→compile→ready
    intervals, bytes by cache tier (pool/local/peer/source), hedge
    outcomes, fetch∥put overlap — the scale-out evidence layer the
    `--phase scaleout` bench will gate on (ISSUE 13)."""
    q = []
    if stub_id:
        q.append(f"stub_id={stub_id}")
    if container_id:
        q.append(f"container_id={container_id}")
    qs = ("?" + "&".join(q)) if q else ""
    data = _client()._run(
        lambda c: c.request("GET", f"/api/v1/coldstart{qs}"))
    replicas = data.get("replicas", {})
    if as_json:
        click.echo(json.dumps(replicas, indent=2))
        return
    if not replicas:
        click.echo("no coldstart records yet (restore a checkpointed "
                   "replica, or wait a heartbeat)")
        return
    click.echo(f"{'replica':<16}{'plan':>8}{'fetch':>8}{'put':>8}"
               f"{'compile':>9}{'warmup':>8}{'ready':>8}"
               f"{'overlap':>8}  tier bytes / hedge")
    for cid, rec in sorted(replicas.items()):
        restore = rec.get("restore", {}) or {}
        runner = rec.get("runner", {}) or {}

        def _f(d, key):
            try:
                return float(d.get(key, 0.0) or 0.0)
            except (TypeError, ValueError):
                return 0.0
        tiers = restore.get("tiers", {}) or {}
        hedge = restore.get("hedge", {}) or {}
        tier_txt = "/".join(f"{t}:{int(tiers.get(t, 0)) >> 10}K"
                            for t in ("pool", "local", "peer", "source")
                            if tiers.get(t))
        hedge_txt = (f" hedge {int(hedge.get('wins', 0))}/"
                     f"{int(hedge.get('fired', 0))}"
                     f" waste {int(hedge.get('wasted_bytes', 0)) >> 10}K"
                     if hedge.get("fired") else "")
        click.echo(
            f"{cid[:15]:<16}"
            f"{_f(restore, 'plan_s') * 1000:>7.1f}ms"
            f"{_f(restore, 'weight_stream_fetch_s') * 1000:>7.1f}ms"
            f"{_f(restore, 'weight_stream_put_s') * 1000:>7.1f}ms"
            f"{_f(runner, 'compile_ahead_s') * 1000:>8.1f}ms"
            f"{_f(runner, 'warmup_s') * 1000:>7.1f}ms"
            f"{_f(runner, 'ready_s') * 1000:>7.1f}ms"
            f"{_f(restore, 'overlap_frac'):>8.2f}"
            f"  {tier_txt}{hedge_txt}")


@cli.command("scaleout")
@click.option("--stub-id", default="", help="filter one deployment")
@click.option("--container-id", default="", help="pin one replica")
@click.option("--json", "as_json", is_flag=True, help="raw report")
def scaleout_cmd(stub_id: str, container_id: str, as_json: bool) -> None:
    """Scale-out plane report (ISSUE 17): per-replica multicast-tree
    position (parent per group / children re-served), groups held vs
    serving-ready, execute-while-scaling readiness fraction, and bytes
    by tree edge — the `tpu9 coldstart` companion for watching N
    replicas share one peer tree instead of N source reads."""
    q = []
    if stub_id:
        q.append(f"stub_id={stub_id}")
    if container_id:
        q.append(f"container_id={container_id}")
    qs = ("?" + "&".join(q)) if q else ""
    data = _client()._run(
        lambda c: c.request("GET", f"/api/v1/scaleout{qs}"))
    if as_json:
        click.echo(json.dumps(data, indent=2))
        return
    if not data.get("enabled", False):
        click.echo("scale-out plane disabled (set TPU9_SCALEOUT=1 or "
                   "scaleout.enabled in config)")
        return
    tree = data.get("tree", {}) or {}
    click.echo(f"tree: fanout={tree.get('fanout', 0)} "
               f"edges={len(tree.get('edges', []))} "
               f"source_edges={tree.get('source_edges', 0)}")
    _scaleout_decisions()
    replicas = data.get("replicas", [])
    if not replicas:
        click.echo("no replicas in the group ledger yet (wait a "
                   "cache-plane heartbeat)")
        return
    click.echo(f"{'replica':<16}{'held':>6}{'ready':>7}{'frac':>7}"
               f"{'children':>10}  parents / bytes by edge")
    for row in replicas:
        parents = row.get("tree_parents", {}) or {}
        edge_bytes = row.get("bytes_by_edge", {}) or {}
        par_txt = ",".join(sorted({p for p in parents.values()})) \
            if parents else "-"
        edge_txt = " ".join(f"{a}:{int(n) >> 10}K"
                            for a, n in sorted(edge_bytes.items()))
        src = int(row.get("bytes_source", 0) or 0)
        if src:
            edge_txt = (edge_txt + f" source:{src >> 10}K").strip()
        stale = " (stale)" if row.get("stale") else ""
        click.echo(
            f"{str(row.get('replica', ''))[:15]:<16}"
            f"{len(row.get('groups_held', [])):>6}"
            f"{len(row.get('groups_ready', [])):>7}"
            f"{float(row.get('ready_frac', 1.0)):>7.2f}"
            f"{len(row.get('children', [])):>10}"
            f"  {par_txt} {edge_txt}{stale}")


def _scaleout_decisions(limit: int = 8) -> None:
    """Trailing autoscaler ledger records (ISSUE 19): the last scaling
    verdicts with their projection/guard signals, folded into the
    scale-out report so `tpu9 scaleout` answers 'why this replica
    count'. Best-effort — a ledger that hasn't seen a tick is silent."""
    try:
        data = _client()._run(lambda c: c.request(
            "GET", f"/api/v1/decisions?plane=autoscaler&limit={limit}"))
    except Exception:   # noqa: BLE001 — report must render regardless
        return
    records = data.get("records", [])
    if not records:
        return
    click.echo("recent autoscaler decisions:")
    for rec in records:
        stamp = time.strftime("%H:%M:%S",
                              time.localtime(float(rec.get("ts", 0.0))))
        click.echo(f"  {stamp} {_fmt_decision(rec)}")


@cli.command("postmortem")
@click.argument("container_id", required=False, default="")
@click.option("--stub-id", default="", help="filter one deployment")
@click.option("--json", "as_json", is_flag=True, help="raw records")
def postmortem_cmd(container_id: str, stub_id: str, as_json: bool) -> None:
    """Replica black-box records (ISSUE 14): the forensic dumps a
    crashed/OOMed/watchdog-tripped engine leaves behind — last flight
    windows, KV-pool + scheduler state, HBM breakdown, exception. With
    no CONTAINER_ID, lists every record; with one, renders its newest
    record in full."""
    q = []
    if container_id:
        q.append(f"container_id={container_id}")
    if stub_id:
        q.append(f"stub_id={stub_id}")
    qs = ("?" + "&".join(q)) if q else ""
    data = _client()._run(
        lambda c: c.request("GET", f"/api/v1/postmortem{qs}"))
    replicas = data.get("replicas", {})
    if as_json:
        click.echo(json.dumps(replicas, indent=2))
        return
    if not replicas:
        click.echo("no post-mortem records (no engine has crashed or "
                   "tripped the watchdog)")
        return
    def _f(d, key):
        # records arrive from the store unvalidated (any container-token
        # holder can ship one): a non-numeric value must render as 0,
        # not kill the whole listing with a format error
        try:
            return float(d.get(key, 0) or 0)
        except (TypeError, ValueError):
            return 0.0

    if not container_id:
        click.echo(f"{'replica':<16}{'when':<10}{'reason':<28}"
                   f"{'hbm used/pred GB':>18}  exception")
        for cid, records in sorted(replicas.items()):
            for rec in records:
                hbm = rec.get("hbm", {}) or {}
                exc = (rec.get("exception", "") or "").splitlines()
                click.echo(
                    f"{cid[:15]:<16}"
                    f"{time.strftime('%H:%M:%S', time.localtime(_f(rec, 'ts'))):<10}"
                    f"{(rec.get('reason', '') or '')[:27]:<28}"
                    f"{_f(hbm, 'hbm_used_gb_per_chip'):>8.2f}/"
                    f"{_f(hbm, 'hbm_predicted_gb_per_chip'):<8.2f} "
                    f" {exc[0][:60] if exc else ''}")
        return
    records = replicas.get(container_id, [])
    if not records:
        click.echo(f"no records for {container_id}")
        return
    rec = records[-1]
    click.echo(f"replica   {container_id}")
    click.echo(f"reason    {rec.get('reason', '')}")
    click.echo(f"exception {rec.get('exception', '')}")
    sched = rec.get("scheduler", {}) or {}
    click.echo(f"scheduler active={sched.get('active_slots', [])} "
               f"queued={sched.get('queued', 0)} "
               f"wait_room={sched.get('wait_room', 0)} "
               f"inflight_steps={sched.get('inflight_steps', 0)} "
               f"deferred={sched.get('deferred_windows', 0)}")
    kv = rec.get("kv_pool", {}) or {}
    if kv:
        click.echo(f"kv pool   used={kv.get('used', 0)} "
                   f"free={kv.get('free', 0)} "
                   f"reserved={kv.get('reserved', 0)} "
                   f"blocks={kv.get('n_blocks', 0)}")
    hbm = rec.get("hbm", {}) or {}
    click.echo(f"hbm       used={hbm.get('hbm_used_gb_per_chip', 0)}GB "
               f"peak={hbm.get('hbm_peak_gb_per_chip', 0)}GB "
               f"predicted={hbm.get('hbm_predicted_gb_per_chip', 0)}GB "
               f"limit={hbm.get('hbm_limit_gb_per_chip', 0)}GB")
    flight = rec.get("flight", []) or []
    click.echo(f"flight    last {len(flight)} windows "
               f"(spans: {len(rec.get('spans', []) or [])})")
    for fr in flight[-16:]:
        click.echo(f"  #{fr.get('seq', 0):<6}{fr.get('kind', ''):<8}"
                   f"k={fr.get('k', 0):<3} pick={fr.get('pick', ''):<10}"
                   f"batch={fr.get('batch', 0)}")


@cli.command("failover")
@click.option("--stub-id", default="", help="filter one deployment")
@click.option("--limit", default=2000, help="trace spans to scan")
@click.option("--json", "as_json", is_flag=True, help="raw spans")
def failover_cmd(stub_id: str, limit: int, as_json: bool) -> None:
    """Recent automatic-failover events (ISSUE 15): every retry the
    gateway performed on behalf of a request whose replica died or
    stalled — attempt number, reason, failed replica, and the stream
    token watermark the resume spliced at. Zero rows on a healthy fleet;
    rows with a flat shed rate mean replicas are dying under requests,
    not capacity running out."""
    data = _client()._run(
        lambda c: c.request("GET", f"/api/v1/traces?limit={limit}"))
    spans = [s for s in data.get("spans", [])
             if s.get("name") == "gateway.failover"
             and (not stub_id
                  or s.get("attributes", {}).get("stub_id") == stub_id)]
    if as_json:
        click.echo(json.dumps(spans, indent=2))
        return
    if not spans:
        click.echo("no failover events in the trace window (healthy "
                   "fleet, or the ring already rotated them out)")
        return
    click.echo(f"{'when':<10}{'stub':<18}{'att':>4}{'watermark':>10}  "
               f"{'reason':<22}failed replica")
    for sp in spans:
        at = sp.get("attributes", {})
        ts = sp.get("startTimeUnixNano", 0) / 1e9
        click.echo(
            f"{time.strftime('%H:%M:%S', time.localtime(ts)):<10}"
            f"{str(at.get('stub_id', ''))[:17]:<18}"
            f"{at.get('attempt', 0):>4}"
            f"{at.get('watermark', at.get('failed_status', '')):>10}  "
            f"{str(at.get('reason', at.get('failed_status', '')))[:21]:<22}"
            f"{at.get('failed_replica', '')}")


@cli.command("profile")
@click.argument("stub_id")
@click.option("--seconds", default=4.0, help="seconds to trace")
@click.option("--container-id", default="", help="pin one replica")
@click.option("--out-dir", default="", help="dump dir on the replica")
def profile_cmd(stub_id: str, seconds: float, container_id: str,
                out_dir: str) -> None:
    """Trace a live replica with jax.profiler for the next --seconds
    seconds (device planes, the serve loop's host phases, the model's
    scopes); prints the replica-side dump path."""
    body = {"stub_id": stub_id, "seconds": seconds}
    if container_id:
        body["container_id"] = container_id
    if out_dir:
        body["out_dir"] = out_dir
    out = _client()._run(lambda c: c.request("POST", "/api/v1/profile",
                                             json_body=body))
    click.echo(json.dumps(out, indent=2))


# ---------------------------------------------------------------------------
# tpu9 top — live fleet SLO / goodput / timeline view (ISSUE 12)
# ---------------------------------------------------------------------------

_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(samples: list, width: int = 24) -> str:
    """Unicode sparkline of the newest `width` [ts, value] samples."""
    vals = [v for _, v in samples[-width:]]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(_SPARK[int((v - lo) / span * (len(_SPARK) - 1))]
                   for v in vals)


def _render_top(metrics_data: dict, slo_data: dict,
                timeline_data: dict) -> str:
    """Pure renderer (unit-testable): the three endpoint payloads → one
    terminal frame of engine, SLO and goodput tables."""
    lines: list[str] = []
    series = timeline_data.get("series", {})

    engines = metrics_data.get("engines", {})
    lines.append(f"ENGINES ({len(engines)} replicas)")
    lines.append(f"  {'replica':<14}{'health':>9}{'hbm%':>6}{'tok/s':>9}"
                 f"{'kv free':>9}{'spec acc':>9}{'recompiles':>11}"
                 f"{'age':>7}  trend")
    for cid, snap in sorted(engines.items()):
        def _f(key, default=0.0):
            try:
                return float(snap.get(key, default))
            except (TypeError, ValueError):
                return default
        spark = _sparkline(series.get(f"engine.{cid}.tokens_per_sec", []))
        # health plane (ISSUE 14): watchdog verdict + HBM headroom
        # (free fraction of the chip; '-' where the backend reports no
        # memory stats). A non-ok replica shows its reason instead of
        # the throughput sparkline — during an incident, WHY beats trend.
        health = str(snap.get("health", "") or "-")
        limit = _f("hbm_limit_gb_per_chip")
        headroom = (f"{max(1.0 - _f('hbm_used_gb_per_chip') / limit, 0.0):>5.0%}"
                    if limit > 0 else f"{'-':>5}")
        tail = spark if health in ("ok", "-") else \
            f"!! {snap.get('health_reason', '') or health}"
        lines.append(
            f"  {cid[:13]:<14}{health[:8]:>9}{headroom:>6}"
            f"{_f('tokens_per_sec'):>9.1f}"
            f"{_f('kv_blocks_free'):>9.0f}"
            f"{_f('spec_acceptance_rate'):>9.2f}"
            f"{_f('graph_compiles_post_warmup'):>11.0f}"
            f"{_f('age_s'):>6.1f}s  {tail}")

    # KV tiering plane (ISSUE 20): only rendered when some replica runs a
    # host tier, so an untiered fleet's frame is unchanged
    tiered = {cid: snap for cid, snap in engines.items()
              if "kvtier_host_bytes" in snap
              or "kvtier_downpages" in snap}
    if tiered:
        lines.append("")
        lines.append("KV TIERS (occupancy / paging / prefix hits by tier)")
        lines.append(f"  {'replica':<14}{'dev MB':>8}{'host MB':>9}"
                     f"{'down':>7}{'up':>5}{'spill':>7}"
                     f"{'hit d/h':>10}{'up p95':>9}")
        for cid, snap in sorted(tiered.items()):
            def _f(key, default=0.0):
                try:
                    return float(snap.get(key, default))
                except (TypeError, ValueError):
                    return default
            lines.append(
                f"  {cid[:13]:<14}"
                f"{_f('kvtier_device_bytes') / 1e6:>8.1f}"
                f"{_f('kvtier_host_bytes') / 1e6:>9.1f}"
                f"{_f('kvtier_downpages'):>7.0f}"
                f"{_f('kvtier_uppages'):>5.0f}"
                f"{_f('kvtier_peer_spills'):>7.0f}"
                f"{_f('kvtier_hits_device'):>6.0f}/"
                f"{_f('kvtier_hits_host'):<3.0f}"
                f"{_f('kvtier_uppage_p95_s') * 1e3:>8.1f}ms")

    lines.append("")
    lines.append("SLO (burn rate: >1 on fast+slow windows = burning)")
    lines.append(f"  {'stub':<14}{'objective':<14}{'fast':>8}{'slow':>8}"
                 f"{'pressure':>9}  status")
    for sid, row in sorted(slo_data.get("stubs", {}).items()):
        for name, obj in sorted(row.get("objectives", {}).items()):
            status = ("BURNING" if obj.get("burning")
                      else "warning" if obj.get("warning") else "ok")
            if obj.get("attribution"):
                status += f" ({obj['attribution']})"
            lines.append(
                f"  {sid[:13]:<14}{name[:13]:<14}"
                f"{obj['fast']['burn']:>8.2f}{obj['slow']['burn']:>8.2f}"
                f"{row.get('pressure', 0.0):>9.2f}  {status}")

    lines.append("")
    lines.append("GOODPUT (per workspace; fractions sum to 1)")
    lines.append(f"  {'workspace':<14}{'tok/chip-s':>11}{'goodput':>9}"
                 f"{'q-wait':>8}{'shed':>7}{'spec-rb':>8}{'recomp':>8}"
                 f"{'idle':>7}")
    for ws, row in sorted(metrics_data.get("goodput", {}).items()):
        waste = row.get("waste", {})
        lines.append(
            f"  {ws[:13]:<14}"
            f"{row.get('goodput_tokens_per_chip_second', 0.0):>11.2f}"
            f"{row.get('goodput_frac', 0.0):>9.1%}"
            f"{waste.get('queue_wait', 0.0):>8.1%}"
            f"{waste.get('shed', 0.0):>7.1%}"
            f"{waste.get('spec_rollback', 0.0):>8.1%}"
            f"{waste.get('recompile_stall', 0.0):>8.1%}"
            f"{waste.get('idle_reservation', 0.0):>7.1%}")

    lines.append("")
    lines.append("ROUTER timeline (queue depth / ttft p95)")
    stubs = sorted({n.split(".")[1] for n in series
                    if n.startswith("router.")})
    for sid in stubs:
        q = _sparkline(series.get(f"router.{sid}.queue_depth", []))
        t = _sparkline(series.get(f"router.{sid}.ttft_p95_s", []))
        lines.append(f"  {sid[:13]:<14} queue {q or '-':<26} "
                     f"ttft {t or '-'}")
    return "\n".join(lines)


@cli.command("top")
@click.option("--interval", default=2.0, help="refresh seconds")
@click.option("--once", is_flag=True, help="render one frame and exit")
def top_cmd(interval: float, once: bool) -> None:
    """Live fleet view: engine replicas, SLO burn rates and per-tenant
    goodput on the gateway's metrics timeline (ISSUE 12)."""
    import time as _time
    client = _client()
    while True:
        m = client._run(lambda c: c.request("GET", "/api/v1/metrics"))
        s = client._run(lambda c: c.request("GET", "/api/v1/slo"))
        t = client._run(lambda c: c.request(
            "GET", "/api/v1/timeline?series=router.*,engine.*&limit=48"))
        frame = _render_top(m, s, t)
        if once:
            click.echo(frame)
            return
        click.clear()
        click.echo(frame)
        _time.sleep(interval)


@cli.command("metrics")
@click.option("--prometheus", is_flag=True)
def metrics_cmd(prometheus: bool) -> None:
    path = "/api/v1/metrics" + ("?format=prometheus" if prometheus else "")
    if prometheus:
        click.echo(_client()._run(lambda c: c.request_bytes(
            "GET", path)).decode())
    else:
        click.echo(json.dumps(
            _client()._run(lambda c: c.request("GET", path)), indent=2))


# ---------------------------------------------------------------------------
# servers
# ---------------------------------------------------------------------------

@cli.group()
def llm() -> None:
    """Native LLM serving (reference ``beta9 llm``: one-command LLM
    deploys; tpu9 serves its own engine instead of wrapping vllm)."""


_LLM_APP_TEMPLATE = '''"""Generated by `tpu9 llm deploy` — the native engine for {model}."""
from tpu9 import endpoint


@endpoint(tpu="{tpu}", runner="llm", model="{model}",
          extra={{"max_batch": {max_batch}, "max_seq_len": {max_seq_len},
                 "kv_pool_blocks": {kv_pool_blocks}, "kv_block_size": 128}},
          concurrent_requests={concurrency}, timeout=1800,
          keep_warm_seconds={keep_warm})
def load():
    from tpu9.serving.presets import load_engine
    return load_engine("{model}", max_batch={max_batch},
                       max_seq_len={max_seq_len},
                       prefill_buckets=(128, {max_seq_len}),
                       kv_pool_blocks={kv_pool_blocks})
'''


@llm.command("deploy")
@click.option("--model", required=True,
              help="engine preset (llama3-8b-int8, llama3-70b-int8, "
                   "gemma-7b, mixtral-8x7b-int8, ...)")
@click.option("--tpu", default="v5e-1",
              help="slice spec; '' serves on CPU (local dev)")
@click.option("--name", default="")
@click.option("--max-batch", default=8)
@click.option("--max-seq-len", default=2048)
@click.option("--kv-pool-blocks", default=0,
              help="pin the paged KV pool to this many 128-token blocks "
                   "(0: as many as max_batch x max_seq_len tokens need); "
                   "what a model whose KV state is many planes deep "
                   "deploys with")
@click.option("--concurrency", default=64)
@click.option("--keep-warm", default=300)
def llm_deploy(model: str, tpu: str, name: str, max_batch: int,
               max_seq_len: int, kv_pool_blocks: int, concurrency: int,
               keep_warm: int) -> None:
    """One-command LLM serving: generates the engine app, validates HBM
    feasibility at the gateway, deploys behind @endpoint."""
    import tempfile

    if tpu:
        from ..serving.feasibility import validate_llm_deployment
        # client-side pre-check: the arithmetic BEFORE uploading anything
        budget = validate_llm_deployment(model, tpu, max_batch=max_batch,
                                         max_seq_len=max_seq_len,
                                         kv_pool_blocks=kv_pool_blocks,
                                         # the app's chunk caps a block
                                         kv_block_size=128)
        click.echo(f"fits: {budget.as_dict()}", err=True)
    else:
        from ..serving.presets import resolve_preset
        resolve_preset(model)     # unknown presets still fail fast

    app = _LLM_APP_TEMPLATE.format(model=model, tpu=tpu,
                                   max_batch=max_batch,
                                   max_seq_len=max_seq_len,
                                   kv_pool_blocks=kv_pool_blocks,
                                   concurrency=concurrency,
                                   keep_warm=keep_warm)
    name = name or model.replace(".", "-")
    with tempfile.TemporaryDirectory(prefix="tpu9-llm-") as tmp:
        path = os.path.join(tmp, "llm_app.py")
        with open(path, "w") as f:
            f.write(app)
        obj = _load_target(f"{path}:load")
        out = obj.deploy(name, sync_root=tmp)
    click.echo(json.dumps(out, indent=2))


@llm.command("complete")
@click.argument("name")
@click.option("--tokens", required=True,
              help="comma-separated prompt token ids")
@click.option("--max-new-tokens", default=64)
@click.option("--stream", is_flag=True)
@click.pass_context
def llm_complete(ctx, name: str, tokens: str, max_new_tokens: int,
                 stream: bool) -> None:
    """Generate from a deployed LLM endpoint."""
    payload = {"tokens": [int(t) for t in tokens.split(",") if t.strip()],
               "max_new_tokens": max_new_tokens}
    if stream:
        payload["stream"] = True
    ctx.invoke(invoke, name=name, payload=json.dumps(payload),
               stream=stream)


@llm.command("stats")
@click.argument("name")
def llm_stats(name: str) -> None:
    """Engine stats from the serving container (token pressure, KV block
    occupancy, prefix-cache hits)."""
    out = _client()._run(
        lambda c: c.request("GET", f"/endpoint/{name}/health"))
    click.echo(json.dumps(out, indent=2))


@cli.command("cdi-generate")
@click.option("--out", default="/etc/cdi/tpu9.json",
              help="CDI spec output path ('-' for stdout)")
@click.option("--dev-root", default="/dev")
def cdi_generate(out: str, dev_root: str) -> None:
    """Generate the host's TPU CDI spec (containerd/CRI-O/podman device
    injection — the nvidia-ctk analogue for TPU hosts)."""
    import subprocess
    from ..utils import native_binary
    binary = native_binary("t9cdi")
    if not os.path.exists(binary):
        raise click.ClickException(
            f"{binary} not built — run `make -C native`")
    cmd = [binary, "--dev-root", dev_root]
    if out != "-":
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        cmd += ["--out", out]
    rc = subprocess.run(cmd)
    if rc.returncode != 0:
        raise click.ClickException(f"t9cdi exited {rc.returncode}")
    if out != "-":
        click.echo(f"wrote {out}")


@cli.command()
@click.option("--config", "config_path", default="")
def gateway(config_path: str) -> None:
    """Run the control plane (gateway + scheduler + state server)."""
    from ..gateway import Gateway
    from ..scheduler import LocalProcessPool

    cfg = load_config(config_path or None)

    async def main() -> None:
        gw = Gateway(cfg)
        await gw.start()
        click.echo(f"gateway:      http://{cfg.gateway.host}:{gw.port}")
        click.echo(f"token:        {gw.default_token}")
        click.echo(f"worker-token: {gw.worker_token}")
        if gw.state_server:
            click.echo(f"state:        {gw.state_server.address}")
        await asyncio.Event().wait()

    asyncio.run(main())


@cli.command()
@click.option("--gateway-state", required=True,
              help="state-server address host:port")
@click.option("--gateway-url", default="",
              help="gateway HTTP URL (for object/image fetches)")
@click.option("--token", "worker_token", default="",
              help="worker token (printed at gateway boot)")
@click.option("--pool", default="default")
@click.option("--tpu", "tpu_gen", default="",
              help="TPU generation on this host (v5e, v5p, ...)")
@click.option("--runtime", "runtime_kind", default="process",
              type=click.Choice(["process", "native", "runc"]))
@click.option("--slice-id", default="")
@click.option("--slice-rank", default=0)
@click.option("--slice-hosts", default=1)
@click.option("--config", "config_path", default="")
def worker(gateway_state: str, gateway_url: str, worker_token: str,
           pool: str, tpu_gen: str, runtime_kind: str,
           slice_id: str, slice_rank: int, slice_hosts: int,
           config_path: str) -> None:
    """Run a worker host agent joined to a gateway."""
    import tempfile

    import aiohttp

    from ..images import ImageManifest
    from ..repository import WorkerRepository
    from ..runtime import new_runtime
    from ..statestore import RemoteStore
    from ..worker import Worker
    from ..worker.cache_manager import WorkerCache

    cfg = load_config(config_path or None)
    if cfg.storage.mode == "gcs" and cfg.worker.storage_shared:
        # a GCS-backed gateway with a "shared"-storage worker silently
        # splits volumes into two disjoint stores — force sync mode
        click.echo("storage.mode=gcs: forcing worker.storage_shared=false "
                   "(volumes sync from the bucket)", err=True)
        cfg.worker.storage_shared = False

    async def main() -> None:
        store = await RemoteStore(
            gateway_state,
            auth_token=cfg.database.state_auth_token).connect()
        runtime = new_runtime(runtime_kind,
                              base_dir=cfg.worker.containers_dir)

        object_resolver = None
        chunk_source = None
        manifest_fetch = None
        volume_sync = None
        volume_push = None
        volume_manifest = None
        if gateway_url and worker_token:
            session = aiohttp.ClientSession(
                headers={"Authorization": f"Bearer {worker_token}"})
            objects_dir = tempfile.mkdtemp(prefix="tpu9-objects-")

            async def object_resolver(object_id: str) -> str:
                path = os.path.join(objects_dir, f"{object_id}.zip")
                if os.path.exists(path):
                    return path
                async with session.get(
                        f"{gateway_url}/rpc/object/{object_id}") as resp:
                    if resp.status != 200:
                        return ""
                    with open(path, "wb") as f:
                        f.write(await resp.read())
                return path

            async def chunk_source(digest: str):
                async with session.get(
                        f"{gateway_url}/rpc/image/chunk/{digest}") as resp:
                    return await resp.read() if resp.status == 200 else None

            async def manifest_fetch(image_id: str):
                async with session.get(
                        f"{gateway_url}/rpc/image/manifest/{image_id}") as resp:
                    if resp.status != 200:
                        return None
                    return ImageManifest.from_json(await resp.text())

            volumes_dir = os.path.join(cfg.worker.containers_dir,
                                       "volume-sync")

            def _vol_dest(workspace_id: str, name: str) -> str:
                # single-component names only — mirrors the lifecycle's
                # validation so a crafted name can't traverse volumes_dir
                from ..utils.paths import validate_path_part
                for part in (workspace_id, name):
                    validate_path_part(part, "volume path part")
                return os.path.join(volumes_dir, workspace_id, name)

            async def volume_sync(workspace_id: str, name: str) -> str:
                """Pull a workspace volume from the gateway's object store
                into a local dir (cross-host mode). A file re-downloads when
                missing, size differs, or the remote mtime moved past the
                last sync (same-size updates must not serve stale bytes)."""
                from urllib.parse import quote
                dest = _vol_dest(workspace_id, name)
                os.makedirs(dest, exist_ok=True)
                base = (f"{gateway_url}/rpc/internal/volume/"
                        f"{workspace_id}/{name}/files")
                async with session.get(base) as resp:
                    if resp.status != 200:
                        return dest
                    entries = await resp.json()
                for e in entries:
                    rel = e["path"]
                    local = os.path.realpath(os.path.join(dest, rel))
                    if not local.startswith(os.path.realpath(dest) + os.sep):
                        continue
                    remote_mtime = e.get("mtime") or 0
                    if (os.path.isfile(local)
                            and os.path.getsize(local) == e["size"]
                            and isinstance(remote_mtime, (int, float))
                            and os.path.getmtime(local) >= remote_mtime):
                        continue
                    os.makedirs(os.path.dirname(local), exist_ok=True)
                    async with session.get(
                            f"{base}/{quote(rel, safe='/')}") as resp:
                        if resp.status == 200:
                            with open(local, "wb") as f:
                                f.write(await resp.read())
                return dest

            async def volume_manifest(workspace_id: str, name: str):
                """Chunk manifest for CacheFS read-through volume mounts
                (VERDICT r04 #5) — None on any failure → sync-down."""
                async with session.get(
                        f"{gateway_url}/rpc/internal/volume/"
                        f"{workspace_id}/{name}/manifest") as resp:
                    if resp.status != 200:
                        return None
                    return ImageManifest.from_json(await resp.text())

            async def volume_push(workspace_id: str, name: str,
                                  local_dir: str) -> None:
                """Push container writes back to the object store on exit
                (last-writer-wins; deletions are not propagated)."""
                from urllib.parse import quote
                base = (f"{gateway_url}/rpc/internal/volume/"
                        f"{workspace_id}/{name}/files")
                remote: dict[str, dict] = {}
                async with session.get(base) as resp:
                    if resp.status == 200:
                        remote = {e["path"]: e for e in await resp.json()}
                root = os.path.realpath(local_dir)
                for dirpath, _dirs, files in os.walk(root):
                    for fn in files:
                        full = os.path.join(dirpath, fn)
                        if not os.path.isfile(full):
                            # overlay WHITEOUTS (0:0 char devices marking
                            # deletions in a CacheFS volume's upper dir)
                            # and other specials: skip — opening one
                            # raises and would abort the whole write-back
                            continue
                        rel = os.path.relpath(full, root).replace(
                            os.sep, "/")
                        st = os.stat(full)
                        r = remote.get(rel)
                        r_mtime = (r or {}).get("mtime") or 0
                        if (r is not None and r["size"] == st.st_size
                                and isinstance(r_mtime, (int, float))
                                and r_mtime >= st.st_mtime):
                            continue
                        with open(full, "rb") as f:
                            data = f.read()
                        await session.put(
                            f"{base}/{quote(rel, safe='/')}", data=data)

        disks = None
        sandboxes = None
        criu = None
        ckpt_record = None
        ckpt_update = None
        ckpt_store = None
        ckpt_fetch = None
        if gateway_url and worker_token:
            from ..worker.disks import DiskManager

            # container checkpoints: rows + manifests live on the gateway,
            # chunk payloads ride the distributed worker cache (HRW peers)

            async def ckpt_record(stub_id, workspace_id, container_id):
                async with session.post(
                        f"{gateway_url}/rpc/internal/ckpt/{workspace_id}/"
                        f"{stub_id}/{container_id}") as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"checkpoint record failed: {resp.status}")
                    return (await resp.json())["checkpoint_id"]

            async def ckpt_update(checkpoint_id, status,
                                  remote_key="", size=0) -> None:
                async with session.post(
                        f"{gateway_url}/rpc/internal/ckpt/status/"
                        f"{checkpoint_id}",
                        json={"status": status, "remote_key": remote_key,
                              "size": size}) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"checkpoint status update failed: {resp.status}")

            async def ckpt_store(checkpoint_id, blob: str) -> None:
                async with session.post(
                        f"{gateway_url}/rpc/internal/ckpt/manifest/"
                        f"{checkpoint_id}", data=blob) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"checkpoint manifest upload failed: "
                            f"{resp.status}")

            async def ckpt_fetch(checkpoint_id):
                async with session.get(
                        f"{gateway_url}/rpc/internal/ckpt/manifest/"
                        f"{checkpoint_id}") as resp:
                    return (await resp.text() if resp.status == 200
                            else None)

            async def disk_chunk_put(data: bytes, digest: str) -> None:
                async with session.post(
                        f"{gateway_url}/rpc/image/chunk/{digest}",
                        data=data) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"disk chunk upload failed: {resp.status}")

            async def disk_chunk_get(digest: str):
                async with session.get(
                        f"{gateway_url}/rpc/image/chunk/{digest}") as resp:
                    return await resp.read() if resp.status == 200 else None

            async def disk_manifest_put(workspace_id, name, snapshot_id,
                                        manifest_json, size) -> None:
                async with session.post(
                        f"{gateway_url}/rpc/internal/disk/{workspace_id}/"
                        f"{name}/manifest/{snapshot_id}",
                        data=manifest_json) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"disk manifest upload failed: {resp.status}")

            async def disk_manifest_get(snapshot_id: str):
                async with session.get(
                        f"{gateway_url}/rpc/internal/disk/manifest/"
                        f"{snapshot_id}") as resp:
                    return (await resp.text() if resp.status == 200
                            else None)

            disks = DiskManager(cfg.worker.disks_dir,
                                chunk_put=disk_chunk_put,
                                chunk_get=disk_chunk_get,
                                manifest_put=disk_manifest_put,
                                manifest_get=disk_manifest_get)

            from ..worker.sandbox import SandboxAgent

            async def sbxsnap_put(snapshot_id, workspace_id, container_id,
                                  manifest_json, size,
                                  kind: str = "workdir") -> None:
                async with session.post(
                        f"{gateway_url}/rpc/internal/sbxsnap/{workspace_id}/"
                        f"{container_id}/{snapshot_id}?kind={kind}",
                        data=manifest_json) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"sandbox snapshot upload failed: {resp.status}")

            async def sbxsnap_get(snapshot_id: str):
                async with session.get(
                        f"{gateway_url}/rpc/internal/sbxsnap/manifest/"
                        f"{snapshot_id}") as resp:
                    return (await resp.text() if resp.status == 200
                            else None)

            sandboxes = SandboxAgent(runtime, store,
                                     chunk_put=disk_chunk_put,
                                     chunk_get=disk_chunk_get,
                                     snap_put=sbxsnap_put,
                                     snap_get=sbxsnap_get)

            from ..config import env_criu_bin
            from ..worker.criu import CriuManager
            criu = CriuManager(
                os.path.join(cfg.worker.checkpoint_dir, "criu"),
                criu_bin=env_criu_bin(),
                chunk_put=disk_chunk_put, chunk_get=disk_chunk_get,
                snap_put=sbxsnap_put, snap_get=sbxsnap_get)

        from ..types import new_id
        if sandboxes is None:
            # no gateway sink: process manager + fs API still work,
            # snapshots report "no snapshot sink"
            from ..worker.sandbox import SandboxAgent
            sandboxes = SandboxAgent(runtime, store)
        cache = WorkerCache(cfg.cache, new_id("wc"), WorkerRepository(store),
                            source=chunk_source,
                            manifest_fetch=manifest_fetch)
        checkpoints = None
        if ckpt_record is not None:
            # readiness-trigger checkpoint/restore (ISSUE 1 streaming fast
            # path) — the warm weights pool keeps deserialized param trees
            # for same-node replica restores
            from ..worker.checkpoint import CheckpointManager
            from ..worker.weightpool import WeightPool
            weight_pool = (WeightPool(cfg.worker.weight_pool_mb << 20)
                           if cfg.worker.weight_pool_mb > 0 else None)

            async def tree_hints(group_key: str):
                # scale-out distribution tree (ISSUE 17): the gateway
                # coordinator publishes its plan under scaleout:tree;
                # this replica's preference list for the group is looked
                # up by its own cache serve address. Best-effort — no
                # plan (or scaleout off) degrades to HRW order.
                from ..scaleout import scaleout_on
                from ..scaleout.coordinator import PLAN_KEY
                from ..scaleout.tree import TreePlan
                if not scaleout_on(cfg.scaleout):
                    return []
                blob = await store.get(PLAN_KEY)
                if not blob:
                    return []
                plan = TreePlan.from_dict(
                    blob if isinstance(blob, dict) else json.loads(blob))
                return plan.peer_prefs(cache.client.self_address,
                                       group_key)

            checkpoints = CheckpointManager(
                cache.client, record=ckpt_record, update=ckpt_update,
                store_manifest=ckpt_store, fetch_manifest=ckpt_fetch,
                weight_pool=weight_pool, tree_hints=tree_hints)
        w = Worker(store, runtime, cfg=cfg.worker, pool=pool,
                   tpu_generation=tpu_gen, slice_id=slice_id,
                   slice_host_rank=slice_rank, slice_host_count=slice_hosts,
                   cache=cache, object_resolver=object_resolver,
                   volume_sync=volume_sync, volume_push=volume_push,
                   volume_manifest=volume_manifest,
                   checkpoints=checkpoints,
                   disks=disks, sandboxes=sandboxes, criu=criu)
        await w.start()
        click.echo(f"worker {w.worker_id} joined (pool={pool}, "
                   f"chips={w.tpu.chip_count})")
        try:
            while True:
                await asyncio.sleep(5)
                if w.should_shut_down():
                    click.echo("idle; shutting down")
                    break
        finally:
            await w.stop()

    asyncio.run(main())


if __name__ == "__main__":
    cli()
