#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process brings up a gateway and a worker on the CPU backend and the LLM
runner container on the cell's chips, deploys a handler that returns the
configuration's engine (weights from ``--seed``), serves six probes one at a
time and holds them to the plain float32 reference, warms up, offers the
cell's traffic for ``--seconds`` seconds, prints ONE JSON line — ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced) — and tears everything down. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler trace
of a few seconds inside the window plus the runner's counters.

The cell, its configuration, its traffic mix and its metrics are looked up in
``BENCHMARK.json`` and in the files its names stand for (``manifest.py``):
no name of a cell, a configuration or a mix appears in this code.

Exit code 0 only with a result line. A machine without a TPU, or with fewer
chips than the cell asks for, fails before any engine is built. This parent
never touches a jax backend: one process per chip.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import asyncio       # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import chips, manifest, stack as stack_mod    # noqa: E402

PROBE_TOKENS = 16            # answered per probe
BRINGUP_TIMEOUT_S = 1100     # a first run compiles
REFERENCE_TIMEOUT_S = 600
DRAIN_TIMEOUT_S = 120


class RunFailed(Exception):
    pass


def info(**fields) -> None:
    """An earlier line: context a reader wants, which the driver ignores."""
    print(json.dumps({"info": fields}), flush=True)


def make_probes(rng, vocab: int, chunk: int, max_seq_len: int) -> list:
    """chip_smoke.py's request mix, served one at a time: short; several
    prefill chunks (a fused group of four and a tail); a pair that shares a
    prefix of more than two KV blocks; two more shorts."""
    def toks(n):
        return rng.integers(3, vocab, size=n).tolist()

    multi = min(5 * chunk + chunk // 3, max_seq_len - PROBE_TOKENS - chunk)
    shared = toks(2 * chunk + chunk // 3)
    return [{"name": "short", "prompt": toks(12)},
            {"name": "multi_chunk", "prompt": toks(multi)},
            {"name": "mid", "prompt": toks(40)},
            {"name": "prefix_a", "prompt": shared + toks(20)},
            {"name": "short_2", "prompt": toks(25)},
            {"name": "prefix_b", "prompt": shared + toks(20)}]


def mailbox(run_dir: str, op: str, payload: dict, timeout: float) -> dict:
    """Ask the benchmark's thread inside the runner (``serve.py``)."""
    result = os.path.join(run_dir, f"{op}.result.json")
    if os.path.exists(result):
        os.remove(result)
    tmp = os.path.join(run_dir, f"{op}.request.json.tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(run_dir, f"{op}.request.json"))
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(result):
            with open(result) as f:
                out = json.load(f)
            if "error" in out:
                raise RunFailed(f"{op} inside the runner: {out['error']}")
            return out
        time.sleep(0.05)
    raise RunFailed(f"no answer to {op} from the runner in {timeout} s")


def apply_rehearsal(config: dict, name: str) -> tuple:
    """CPU rehearsal only: the tiny sizes kept beside the configurations, and
    the factor its traffic's lengths are divided by."""
    with open(os.path.join(HERE, "rehearsal", f"{name}.json")) as f:
        tiny = json.load(f)
    config = dict(config, **tiny["model"])
    config["assumed"] = dict(config.get("assumed", {}), **tiny["assumed"])
    config["engine"] = dict(config["engine"], **tiny["engine"])
    config["endpoint"] = dict(config["endpoint"], **tiny["endpoint"])
    return config, tiny["traffic_lengths_divided_by"]


def shrink_traffic(node, factor: int):
    """CPU rehearsal only: every token length of a mix divided by ``factor``."""
    if isinstance(node, dict):
        if node.get("dist") in ("fixed", "uniform", "loguniform"):
            return {k: (max(int(v // factor), 1)
                        if k in ("lo", "hi", "value") else v)
                    for k, v in node.items()}
        return {k: shrink_traffic(v, factor) for k, v in node.items()}
    if isinstance(node, list):
        return [shrink_traffic(v, factor) for v in node]
    return node


class Session:
    """Everything one run holds between bring-up and tear-down."""

    def __init__(self, args):
        self.args = args
        self.manifest = manifest.load()
        self.cell = manifest.cell(self.manifest, args.workload)
        self.config = manifest.load_config(self.manifest, self.cell["config"])
        self.traffic = manifest.load_traffic(self.cell["traffic"])
        if args.rehearse:
            self.config, factor = apply_rehearsal(self.config,
                                                  self.cell["config"])
            self.traffic = shrink_traffic(self.traffic, factor)
        for item in args.set:
            key, _, value = item.partition("=")
            self.traffic[key] = json.loads(value)
        self.kind = manifest.traffic_kind(self.traffic["kind"])
        self.chips = int(self.cell["chips"])
        self.name = "bench"
        self.run_dir = os.path.join(
            HERE, "out", f"{args.workload}.seed{args.seed}.trace{args.trace}")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)

        from tpu9.utils.paths import compile_cache_dir
        cache_dir = compile_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        env = dict(os.environ, PYTHONPATH=ROOT, TPU_LOG_DIR="disabled",
                   JAX_COMPILATION_CACHE_DIR=cache_dir)
        env.pop("BENCH_RUN", None)
        # every program lands in the persistent cache, whatever it took to
        # compile: the second run of a cell in a checkout compiles nothing
        self.container_env = {
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
        if args.rehearse and self.chips > 1:
            self.container_env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={self.chips}"
        self.stack = stack_mod.Stack(self.run_dir, env, self.chips,
                                     fake_chips=args.rehearse)
        self.wall: dict = {}     # step -> seconds since the process started

    def mark(self, step: str) -> None:
        self.wall[step] = round(time.time() - T_START, 2)

    # -- bring-up -----------------------------------------------------------

    def bring_up(self) -> None:
        self.family = manifest.family(self.config)
        self.model = self.family.model_sizes(self.config)
        started = self.stack.start()
        app = stack_mod.APP.format(
            args={"config": self.config, "seed": self.args.seed,
                  "run_dir": self.run_dir},
            tpu=self.config["endpoint"]["tpu"],
            memory=self.config["endpoint"]["memory"], env=self.container_env)
        self.stack.deploy(self.name, app)
        # `native_build`: the tier-1 count and the program's native paths turn
        # on this ignored directory (PR 32), so a check's two sides can be
        # told apart afterwards
        info(stack_s=started["seconds"], worker_chips=started["worker_chips"],
             chips_wait_s=started["chips_wait_s"],
             native_build=os.path.isdir(os.path.join(ROOT, "native", "build")))
        self.mark("stack_up")

    def health(self) -> dict:
        return self.stack.api("GET", f"/endpoint/{self.name}/health",
                              timeout=120)

    def gateway_metrics(self) -> dict:
        return self.stack.api("GET", "/api/v1/metrics")

    def check_device(self, h: dict) -> dict:
        device = {"platform": h["device_platform"], "kind": h["device_kind"],
                  "count": h["device_count"]}
        if not self.args.rehearse:
            if device["platform"] != "tpu":
                raise RunFailed(f"the runner's engine is on {device}, not on "
                                "a TPU")
            if device["count"] != self.chips:
                raise RunFailed(f"the engine spans {device['count']} chips, "
                                f"the cell asks for {self.chips}")
        return device


async def measure(s: Session, plan: dict, seconds: float, trace: bool) -> dict:
    """Probes, the kind's set-up traffic, then the window. Returns what the
    metrics are computed from."""
    from benchmark import client as client_mod
    import numpy as np
    loop = asyncio.get_running_loop()

    def off_loop(fn, *a):
        return loop.run_in_executor(None, fn, *a)

    out: dict = {}
    async with client_mod.Client(s.stack.url, s.stack.token, s.name) as cl:
        # 1. scale from zero with the first probe, then the rest, one at a
        # time; all are held to the reference before the window opens
        knobs = s.config["engine"]
        probes = make_probes(np.random.default_rng(s.args.seed ^ 0x5EED),
                             s.model["vocab_size"], knobs["prefill_chunk"],
                             knobs["max_seq_len"])
        t0 = time.time()
        for i, p in enumerate(probes):
            rec = await asyncio.wait_for(
                cl.send({"prompt": p["prompt"],
                         "max_new_tokens": PROBE_TOKENS}, None),
                BRINGUP_TIMEOUT_S if i == 0 else 300)
            if not rec["ok"]:
                raise RunFailed(f"probe {p['name']} failed: {rec['error']}")
            p["tokens"] = rec["tokens"]
            if i == 0:
                out["bringup_request_s"] = round(time.time() - t0, 3)
        h = await off_loop(s.health)
        out["device"] = s.check_device(h)
        out["health_ready"] = h
        ref = await off_loop(mailbox, s.run_dir, "reference",
                             {"probes": probes}, REFERENCE_TIMEOUT_S)
        out["reference"] = ref
        # the runner holds the chips now: a probe that reads none of them
        # busy here is blind, and the wait at the next start with it
        info(probes=len(probes), bringup_request_s=out["bringup_request_s"],
             reference=ref, chips_held_by_runner=sorted(chips.busy()),
             coldstart={k: v for k, v in h.items()
                        if k.startswith("coldstart_")})

        # 2. set-up traffic of the kind (session contexts), outside the window
        await s.kind.prepare(plan, cl.send)

        # 3. the window. The clock reads 0 when it opens; a kind that ramps
        # up starts its callers before that, at a negative time.
        ramp = float(plan.get("ramp_s", 0.0))
        cl.open_window()
        cl.t0 += ramp
        out["t_window_open"] = time.time() + ramp
        marks: dict = {}

        async def monitor():
            await asyncio.sleep(max(-cl.clock(), 0))
            marks["health0"], marks["gateway0"] = await asyncio.gather(
                off_loop(s.health), off_loop(s.gateway_metrics))
            if trace:
                # a third of the way into the window, the benchmark's thread
                # in the runner traces the mix's number of decode steps, or
                # its number of seconds where those come first
                await asyncio.sleep(max(seconds * 0.3 - cl.clock(), 0))
                marks["tracing"] = off_loop(
                    mailbox, s.run_dir, "trace",
                    {"dir": os.path.join(s.run_dir, "trace"),
                     "seconds": min(float(s.traffic.get("trace_seconds", 5)),
                                    seconds * 0.6),
                     "steps": s.traffic.get("trace_steps")}, 300)
            await asyncio.sleep(max(seconds * 0.5 - cl.clock(), 0))
            marks["health_mid"] = await off_loop(s.health)

        mon = asyncio.create_task(monitor())
        drive = asyncio.create_task(
            s.kind.drive(plan, cl.send, cl.clock, seconds))
        if plan.get("drain", True):
            await asyncio.wait_for(drive, seconds + ramp + DRAIN_TIMEOUT_S)
        else:
            await asyncio.sleep(max(seconds - cl.clock(), 0))
            drive.cancel()
            await asyncio.gather(drive, return_exceptions=True)
        out["window_end_clock"] = cl.clock()
        await mon
        if trace:
            marks["profile"] = await marks.pop("tracing")
        marks["health1"], marks["gateway1"] = await asyncio.gather(
            off_loop(s.health), off_loop(s.gateway_metrics))
        out.update(marks)
        out["records"] = cl.records
    out["memory"] = await off_loop(mailbox, s.run_dir, "memory", {}, 60)
    return out


def layer_context(s: Session, got: dict, seconds: float, trace: dict) -> dict:
    return {"records": got["records"], "seconds": seconds,
            "health_ready": got["health_ready"], "health0": got["health0"],
            "health1": got["health1"], "gateway0": got["gateway0"],
            "gateway1": got["gateway1"], "trace": trace, "model": s.model,
            "family": s.family, "engine": s.config["engine"],
            "device": got["device"], "chips": s.chips, "cell": s.cell["name"]}


def result_line(s: Session, got: dict, seconds: float) -> dict:
    from benchmark import metrics, trace as trace_mod
    traced = bool(s.args.trace)
    records = got["records"]
    # process start -> window open, less the TPU runtime's own start-up in
    # the runner (`device_open_s`, a per-layer metric): it read 5.8-15.5 s on
    # one chip from machine to machine (PR 23) while everything else in
    # set-up repeated to 1 %, and no code of this repository runs in it.
    # Less, too, the wait for free chips before the worker started
    # (`chips_wait_s`; 0 where the last run on the machine had let go)
    opened = got["health_ready"].get("coldstart_device_open_s", 0.0)
    waited = s.stack.chips_wait_s
    setup_s = got["t_window_open"] - T_START - opened - waited
    c = metrics.counts(records)
    tol = s.config["correct_tolerance_logit"]
    compiles = got["health1"]["graph_compiles_post_warmup"]
    correct = bool(got["reference"]["worst_margin"] <= tol and compiles == 0)
    peak = max(got["memory"]["peak_bytes_by_device"] or [0])
    device = dict(got["device"], memory_peak_bytes=peak)

    out_metrics: dict = {}
    line = {"correct": correct, "attempted": c["attempted"],
            "failed": c["failed"], "metrics": out_metrics, "device": device}
    tails = {}
    for q in ("ttft", "tpot"):
        for p in (50, 90, 95):
            tails[f"{q}_p{p}_ms"] = metrics.latency(records, q, p)
    info(counts=c, setup_s=setup_s, device_open_s=opened,
         chips_wait_s=waited, latencies=tails,
         gen_late_p99_ms=metrics.gen_late_ms(records),
         out_tok_s=metrics.out_tok_s(records, seconds),
         window_end_clock=got["window_end_clock"],
         post_warmup_compiles=compiles, memory=got["memory"],
         hbm_peak_gb_per_chip=got["health1"].get("hbm_peak_gb_per_chip"),
         queued_mid=got.get("health_mid", {}).get("queued"),
         queued_end=got["health1"].get("queued"))
    need = s.traffic.get("min_judged_for_tail", 0)

    if not traced:
        for m in manifest.cell_metrics(s.manifest, s.cell["name"], "end_to_end"):
            value = metrics.end_to_end(m["name"], records, seconds, setup_s)
            if not metrics.finite(value) or value <= 0:
                raise RunFailed(f"{m['name']} cannot be taken from this "
                                f"window: {value!r} ({c})")
            if "_p9" in m["name"] and c["judged"] < need:
                info(warning=f"{m['name']} over {c['judged']} judged "
                             f"requests, fewer than {need}")
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return line

    os.environ["JAX_PLATFORMS"] = "cpu"   # reading a trace opens no chip
    trace = trace_mod.reduce_dir(os.path.join(s.run_dir, "trace"),
                                 s.family.STEP_MARKER,
                                 s.family.marker_calls_per_step(s.model))
    s.mark("trace_read")      # the device planes; the readers parse again
    info(profile=got.get("profile"),
         trace={k: v for k, v in trace.items()
                if k not in ("device_ops", "idle_gaps", "op_seconds")})
    ctx = layer_context(s, got, seconds, trace)
    # what the readers read, beside the trace: ``tools/reduce_again.py``
    # takes a saved run's per-layer metrics from them once more
    with open(os.path.join(s.run_dir, "context.json"), "w") as f:
        json.dump(dict(ctx, family=s.config["family"], trace=None,
                       config=s.config), f)
    for m in manifest.cell_metrics(s.manifest, s.cell["name"], "per_layer"):
        value = manifest.layer_reader(m["name"]).read(ctx)
        if metrics.finite(value):     # a reader that finds nothing: left out
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if not s.args.rehearse and not (trace and trace["busy_s"] > 0):
        raise RunFailed("the traced run holds no device operation: "
                        f"{got.get('profile')}")
    if not out_metrics:
        raise RunFailed("no per-layer metric could be read")
    return line


def sweep(s: Session, rates: list, seconds: float) -> None:
    """Builder's tool, not a benchmark run: one bring-up, then the mix at
    each rate in turn, to find the knee. Prints one line per rate."""
    from benchmark import metrics, readers
    for i, rate in enumerate(rates):
        traffic = dict(s.traffic, rate_rps=rate)
        plan = s.kind.plan(traffic, s.args.seed + i, seconds,
                           s.model["vocab_size"])
        got = asyncio.run(measure(s, plan, seconds, False))
        rec = got["records"]
        lat = {f"{q}_p{p}_ms": metrics.latency(rec, q, p)["value"]
               for q in ("ttft", "tpot") for p in (50, 90)}
        h0, hm, h1 = got["health0"], got["health_mid"], got["health1"]
        print(json.dumps({"sweep_rate_rps": rate, **metrics.counts(rec),
                          **lat, "queued_mid": hm["queued"],
                          "active_mid": hm["active_streams"],
                          "drain_s": got["window_end_clock"] - seconds,
                          "out_tok_s": metrics.out_tok_s(rec, seconds),
                          "queue_wait_mean_ms": readers.engine_phase_mean_ms(
                              {"health0": h0, "health1": h1}, "queue_wait")}),
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: tiny sizes, faked chips, the CPU "
                         "backend; walks every step and exits 3, no result")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="builder's experiments: override one parameter of "
                         "the traffic mix (never used by a check)")
    ap.add_argument("--rates", default="",
                    help="builder's knee sweep: comma-separated req/s")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "tpu9")):
        print("benchmark: the system under test (tpu9/) is not in this "
              "directory", file=sys.stderr)
        return 2
    s = Session(args)
    seconds = float(args.seconds if args.seconds is not None
                    else s.manifest["run_seconds"])
    code, line = 1, None
    try:
        s.bring_up()
        if args.rates:
            sweep(s, [float(r) for r in args.rates.split(",")], seconds)
            code = 3
        else:
            plan = s.kind.plan(s.traffic, args.seed, seconds,
                               s.model["vocab_size"])
            got = asyncio.run(measure(s, plan, seconds, bool(args.trace)))
            s.wall["window_open"] = round(got["t_window_open"] - T_START, 2)
            s.wall["window_end"] = round(
                s.wall["window_open"] + got["window_end_clock"], 2)
            s.mark("measured")            # window, drain, the trace written
            if s.stack.failed_starts():
                raise RunFailed("the worker lost containers on the way: "
                                f"{s.stack.failed_starts()}")
            s.stack.stop()                # the chip is let go; now reduce
            s.mark("stack_stopped")
            with open(os.path.join(s.run_dir, "records.json"), "w") as f:
                json.dump([{k: v for k, v in r.items() if k != "tokens"}
                           for r in got["records"]], f)
            line = result_line(s, got, seconds)
            s.mark("reduced")
            code = 3 if args.rehearse else 0
    except (RunFailed, stack_mod.StackError, asyncio.TimeoutError,
            OSError, KeyError) as exc:
        print(f"benchmark: {type(exc).__name__}: {exc}", file=sys.stderr)
        s.stack.dump_logs()
    finally:
        s.stack.stop()
        # the next run on this machine finds the chips free; reducing the
        # trace has already covered most of this wait
        released = s.stack.release()
        s.mark("chips_released")
        info(wall_s=s.wall, chips_release_s=round(released, 3))
    if line is None:
        return code if code != 0 else 1
    with open(os.path.join(s.run_dir, "result.json"), "w") as f:
        json.dump(line, f)
    if args.rehearse:
        info(rehearsal_line=line)
        print("benchmark: rehearsal complete on the CPU backend, not a chip "
              "run: no result", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
