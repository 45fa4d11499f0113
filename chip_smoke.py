#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu9's main path runs on the chip.

    python3 chip_smoke.py            # one TPU v5e chip; what the driver runs
    python3 chip_smoke.py --chips 4  # the mesh-sharded replica on a v5e-4 host

One chip: a gateway process and a worker process (``tpu9 gateway`` / ``tpu9
worker --tpu v5e``, both pinned to the CPU backend) serve ``llama3-8b-int8`` —
full width, full depth, random weights from ``--seed`` — through a
``@endpoint(tpu="v5e-1", runner="llm")`` deployment. The worker finds the chip
by its device nodes and hands it to the runner container, the only process
that holds it. Phases, one JSON line each:

- ``probe``          a child asks ``jax.devices()``; not a TPU -> exit at once
- ``kernels``        each pallas kernel the 8B path uses, compiled, against
                     its XLA oracle (bf16 and int8 pool)
- ``stack``          gateway + worker boot, worker registered with its chips
- ``serve``          deploy; short, multi-chunk, shared-prefix and SSE
                     requests, >= 4 in flight; device facts, bring-up seconds
                     and kernel counts from the runner's ``/health``
- ``reference``      after the runner has released the chip: one teacher-forced
                     ``decoder_forward`` without cache, paging or pallas over
                     prompt + served tokens; every served token's logit within
                     ``TOL_LOGIT`` of its position's maximum
- ``plain_endpoint`` a ``@endpoint(tpu="v5e-1")`` function (zygote-forked
                     runner, no ``runner="llm"``) must answer ``tpu``
- ``warm_restart``   the serve replica comes up again from the compile cache
                     and adds no entry to it

``--chips 4`` runs the mesh path and what it is compared with, and nothing
else: ``llama3-8b`` bf16 cut to 16 layers served ``1x1`` and ``4x1``, both held
to one single-chip reference, then full depth ``4x1`` (16 GB of weights that
fit no single v5e) held to a GSPMD reference on the same mesh; per-chip HBM
after bind and the collectives of the compiled decode step are printed.

The last stdout line on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failing phase ends the run non-zero. Off the chip the script exits
non-zero and never prints that line; ``--rehearse`` (with a small ``--preset``)
still walks every phase's control flow on the CPU backend first — kernels
interpreted, chips faked — and then exits 3.

This parent never imports jax: one process per chip.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# Tolerances, stated before the run.
# kernels: outputs are bf16 averages of unit-normal values, |x| < 8, where
# bf16 values lie 2^-5 apart. Kernel and oracle accumulate in f32 in different
# orders and may round to neighbouring values: two spacings. A wrong mask or
# block shows up as O(1).
TOL_KERNEL = 2 ** -4
# reference: with these random weights a logit has std ~0.25 (unit-RMS hidden
# x lm_head init scale) and the top of 128k of them sits ~4.5 std up, so a
# WRONG token is ~1 below the maximum. bf16 activations through 32 layers put
# noise of a few 1e-2 on a logit, and near-ties at the top are common, so the
# served token must be within half a std of the reference maximum.
TOL_LOGIT = 0.125

NEW_TOKENS = 32
REF_BATCH = 6       # one request mix per reference forward
BRINGUP_TIMEOUT_S = 600
PHASE_CHILD_TIMEOUT_S = 600


class PhaseFailed(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# children: the only code here that touches jax. Each runs in its own process
# (`chip_smoke.py --child NAME JSON`) and prints one JSON line.
# ---------------------------------------------------------------------------

def child_probe(_: dict) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def child_widths(a: dict) -> dict:
    cfg = _smoke_config(a)[0]
    return {k: getattr(cfg, k) for k in ("n_layers", "n_heads", "n_kv_heads",
                                         "head_dim", "vocab_size")}


def child_kernels(a: dict) -> dict:
    """Each pallas kernel of the serving path at ``a``'s widths, compiled
    (interpreted only in a CPU rehearsal), against its XLA oracle."""
    import jax
    import jax.numpy as jnp

    from tpu9.ops.attention import (flash_attention, xla_attention,
                                    xla_decode_attention)
    from tpu9.ops.paged_attention import (paged_decode_attention,
                                          paged_decode_attention_quant,
                                          ragged_decode_attention,
                                          xla_paged_decode_attention)
    from tpu9.ops.quant import quantize_kv
    from tpu9.utils import on_tpu

    interpret = not on_tpu()
    b, qh, kh, d = a["batch"], a["q_heads"], a["kv_heads"], a["head_dim"]
    bs, ctx = a["block"], a["context"]
    mb = ctx // bs
    keys = iter(jax.random.split(jax.random.PRNGKey(a["seed"]), 16))

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    def diff(x, y):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))

    out = {"interpret": interpret, "diffs": {}, "tpu_custom_call": {}}

    def run(name, kernel, oracle, *args, **kw):
        fn = jax.jit(lambda *xs: kernel(*xs, interpret=interpret, **kw))
        compiled = fn.lower(*args).compile()
        out["tpu_custom_call"][name] = \
            compiled.as_text().count("tpu_custom_call")
        out["diffs"][name] = diff(compiled(*args), oracle(*args))

    # prefill: causal flash over a whole prompt
    q, k, v = rnd(2, ctx, qh, d), rnd(2, ctx, kh, d), rnd(2, ctx, kh, d)
    run("flash_attention", flash_attention, xla_attention, q, k, v,
        causal=True)

    # decode against ragged lengths (one full, one empty-but-current, rest
    # mid-block) so the clamped index maps and the length mask both work
    lens = jnp.asarray(([ctx, 1] + [bs + 7 + 11 * i for i in range(b)])[:b],
                       jnp.int32)
    q1 = rnd(b, 1, qh, d)
    run("ragged_decode_attention", ragged_decode_attention,
        xla_decode_attention, q1, rnd(b, ctx, kh, d), rnd(b, ctx, kh, d),
        lens)

    # paged pool: every sequence's blocks scattered over a shared pool
    n_blocks = b * mb + 1
    perm = jax.random.permutation(next(keys), n_blocks - 1) + 1
    table = jnp.concatenate(
        [perm.reshape(b, mb), jnp.zeros((b, 1), perm.dtype)],
        axis=1).astype(jnp.int32)
    kp, vp = rnd(n_blocks, bs, kh, d), rnd(n_blocks, bs, kh, d)
    run("paged_decode_attention", paged_decode_attention,
        xla_paged_decode_attention, q1, kp, vp, table, lens)

    # int8 pool: payload + per-vector scale planes, dequantized in-kernel
    (kq, ks), (vq, vs) = quantize_kv(kp), quantize_kv(vp)
    run("paged_decode_attention_quant", paged_decode_attention_quant,
        lambda q_, k_, v_, ks_, vs_, t_, l_: xla_paged_decode_attention(
            q_, k_, v_, t_, l_, ks_, vs_),
        q1, kq, vq, ks, vs, table, lens)
    return out


def _smoke_config(a: dict):
    """(DecoderConfig, quantized) of the preset, depth cut to ``layers``."""
    from tpu9.serving.presets import resolve_preset
    cfg, quantized = resolve_preset(a["preset"])
    if a.get("layers"):
        cfg = dataclasses.replace(cfg, n_layers=a["layers"])
    return cfg, quantized


def cut_engine(a: dict):
    """The depth-cut comparison model's engine — called INSIDE the runner
    container, from the deployed app's handler. ``load_engine`` takes preset
    names only, so this is its construction with ``n_layers`` replaced."""
    import jax

    from tpu9.serving import EngineConfig, InferenceEngine
    from tpu9.serving.presets import init_params
    from tpu9.serving.shard import make_policy
    cfg, quantized = _smoke_config(a)
    policy = make_policy(a["topology"])
    params = init_params(cfg, quantized, jax.random.PRNGKey(a["seed"]),
                         policy)
    engine = InferenceEngine(params, cfg, EngineConfig(
        max_batch=a["max_batch"], max_seq_len=a["max_seq_len"],
        prefill_buckets=(a["chunk"],), decode_steps=(1, 8),
        kv_block_size=a["chunk"], prefill_chunk=a["chunk"],
        prefix_cache_blocks=a["max_seq_len"] // a["chunk"]), policy=policy)
    engine.precompile()
    return engine


def child_reference(a: dict) -> dict:
    """Teacher-forced logits with no cache, no paging and no pallas, on the
    same seeded weights, over each request's prompt + served tokens. On a
    mesh ``topology`` the forward is plain GSPMD over the sharded weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu9.models.transformer import decoder_forward
    from tpu9.serving.presets import init_params
    from tpu9.serving.shard import make_policy

    cfg, quantized = _smoke_config(a)
    policy = make_policy(a["topology"])
    t0 = time.time()
    params = init_params(cfg, quantized, jax.random.PRNGKey(a["seed"]),
                         policy)
    reqs = a["requests"]
    seqs = [r["prompt"] + r["tokens"] for r in reqs]
    # padded batches of one shape, one compile; causal attention keeps the
    # padding out of every real position. A length off the 128 grid keeps
    # attention() on its XLA path on a TPU too — asserted below from the
    # compiled text. REF_BATCH rows at a time bound the f32 logits
    # ([rows, T, vocab]) beside the weights on one chip.
    t = max(len(s) for s in seqs)
    t += 1 if t % 128 == 0 else 0
    pad = [[0] * t] * (-len(seqs) % REF_BATCH)
    tokens = jnp.asarray([s + [0] * (t - len(s)) for s in seqs] + pad,
                         jnp.int32)
    fwd = jax.jit(lambda p, x: decoder_forward(p, x, cfg)) \
        .lower(params, tokens[:REF_BATCH]).compile()
    kernels = fwd.as_text().count("tpu_custom_call")
    assert kernels == 0, f"reference contains {kernels} pallas calls"
    worst, at = 0.0, None
    for g in range(0, len(seqs), REF_BATCH):
        logits = np.asarray(jax.device_get(
            fwd(params, tokens[g:g + REF_BATCH])))
        assert np.isfinite(logits).all(), "reference logits not finite"
        for i, r in enumerate(reqs[g:g + REF_BATCH]):
            n = len(r["prompt"])
            for j, tok in enumerate(r["tokens"]):
                row = logits[i, n + j - 1]      # predicts position n + j
                margin = float(row.max() - row[tok])
                if margin > worst:
                    worst, at = margin, [r["name"], j]
    return {"seconds": round(time.time() - t0, 1), "tokens_checked":
            sum(len(r["tokens"]) for r in a["requests"]),
            "seq_len": t, "worst_margin": round(worst, 5), "worst_at": at,
            "tpu_custom_call": kernels, "platform": jax.default_backend()}


def child_collectives(a: dict) -> dict:
    """The collectives XLA put into the compiled decode step of the mesh
    engine, read from an AOT compile of the same graph the replica runs
    (compile only: nothing is allocated or executed)."""
    import re

    import jax

    from tpu9.serving import EngineConfig
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard import make_policy
    cfg, quantized = _smoke_config(a)
    policy = make_policy(a["topology"])
    ecfg = EngineConfig(max_batch=a["max_batch"],
                        max_seq_len=a["max_seq_len"],
                        prefill_buckets=(a["chunk"],), decode_steps=(1,),
                        kv_block_size=a["chunk"], prefill_chunk=a["chunk"])
    graphs = GraphFactory(cfg, ecfg, policy, chunk=a["chunk"])
    st = abstract_state(cfg, ecfg, policy)
    jobs = {key: (fn, args) for key, fn, args in graphs.lowering_jobs(
        abstract_params_for(cfg, quantized), st["kv_cache"], st["pool"],
        st["scratch"], st["mb"], [a["chunk"]], (), st["rng"])}
    fn, args = jobs[("decode", 1)]
    text = fn.lower(*args).compile().as_text()
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    found = {op: len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
             for op in ops}
    return {"collectives": {k: v for k, v in found.items() if v},
            "tpu_custom_call": text.count("tpu_custom_call"),
            "platform": jax.default_backend()}


CHILDREN = {"probe": child_probe, "widths": child_widths,
            "kernels": child_kernels,
            "reference": child_reference, "collectives": child_collectives}


def run_child(name: str, args: dict, env: dict) -> dict:
    """Run one child to its end and return the JSON object of its last
    stdout line. The child has exited — and released the chip — on return."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", name,
         json.dumps(args)],
        env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=PHASE_CHILD_TIMEOUT_S)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise PhaseFailed(f"child {name} exited {proc.returncode}: "
                          f"{proc.stderr.strip()[-1500:]}")
    return json.loads(lines[-1])


# ---------------------------------------------------------------------------
# the stack: gateway + worker processes, driven over HTTP like a user would
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _descendants(pid: int) -> list[int]:
    """Every live descendant of ``pid`` (runner containers setsid, so a
    process-group kill alone would miss them)."""
    kids: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(entry))
    out, todo = [], [pid]
    while todo:
        for kid in kids.get(todo.pop(), []):
            out.append(kid)
            todo.append(kid)
    return out


class Stack:
    """A gateway and a worker as separate processes, both on the CPU
    backend, everything they write under ``workdir``."""

    def __init__(self, workdir: str, env: dict, n_chips: int,
                 fake_chips: bool):
        self.workdir = workdir
        self.env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                        PYTHONUNBUFFERED="1")
        self.n_chips = n_chips
        self.fake_chips = fake_chips
        self.procs: list[subprocess.Popen] = []
        self.url = self.token = ""
        self.containers: set[str] = set()      # every id seen, for the logs

    def start(self) -> dict:
        t0 = time.time()
        w = self.workdir
        http_port, state_port = _free_port(), _free_port()
        cfg = {
            "gateway": {"http_port": http_port, "state_port": state_port},
            "database": {"path": f"{w}/gateway.db"},
            "storage": {"local_root": f"{w}/workspaces"},
            "cache": {"data_dir": f"{w}/cache"},
            "image": {"registry_dir": f"{w}/registry"},
            "worker": {k: f"{w}/{v}" for k, v in (
                ("images_dir", "images"), ("containers_dir", "containers"),
                ("storage_root", "workspaces"), ("logs_dir", "logs"),
                ("checkpoint_dir", "checkpoints"), ("disks_dir", "disks"),
                ("vcache_dir", "vcache"))},
        }
        cfg_path = f"{w}/config.json"
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)            # JSON is YAML
        self.url = f"http://127.0.0.1:{http_port}"
        cli = [sys.executable, "-m", "tpu9.cli.main"]
        gw_log = f"{w}/gateway.log"
        self._spawn(cli + ["gateway", "--config", cfg_path], gw_log,
                    self.env)
        boot = self._wait_lines(gw_log, ("token:", "worker-token:", "state:"))
        self.token = boot["token:"]
        wenv = dict(self.env)
        if self.fake_chips:             # CPU rehearsal only
            wenv["TPU9_FAKE_TPU_CHIPS"] = str(self.n_chips)
        self._spawn(cli + ["worker", "--gateway-state", boot["state:"],
                           "--gateway-url", self.url,
                           "--token", boot["worker-token:"],
                           "--tpu", "v5e", "--config", cfg_path],
                    f"{w}/worker.log", wenv)
        deadline = time.time() + 60
        workers: list = []
        while time.time() < deadline and not workers:
            time.sleep(0.5)
            self._check_alive()
            workers = self.api("GET", "/api/v1/worker")
        check(workers, "no worker registered within 60 s")
        chips = workers[0].get("tpu_chip_count")
        check(chips == self.n_chips,
              f"worker inventoried {chips} chips, expected {self.n_chips}")
        return {"seconds": round(time.time() - t0, 1), "worker_chips": chips,
                "tpu_generation": workers[0].get("tpu_generation"),
                "fake_chips": self.fake_chips}

    def _spawn(self, cmd: list, log_path: str, env: dict) -> None:
        with open(log_path, "w") as log:
            self.procs.append(subprocess.Popen(
                cmd, env=env, cwd=self.workdir, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True))

    def _check_alive(self) -> None:
        for p in self.procs:
            check(p.poll() is None,
                  f"{' '.join(p.args[3:5])} exited {p.returncode}: "
                  + self.log_tail(p.args[3]))

    def log_tail(self, which: str, n: int = 1500) -> str:
        try:
            with open(f"{self.workdir}/{which}.log") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def _wait_lines(self, path: str, keys: tuple, timeout: float = 60):
        deadline = time.time() + timeout
        found: dict = {}
        while time.time() < deadline and len(found) < len(keys):
            time.sleep(0.2)
            self._check_alive()
            with open(path) as f:
                for line in f:
                    for key in keys:
                        if line.startswith(key):
                            found[key] = line[len(key):].strip()
        check(len(found) == len(keys), f"gateway never printed {keys}")
        return found

    def _open(self, method: str, path: str, body, timeout: float,
              accept: str = "application/json"):
        return urllib.request.urlopen(urllib.request.Request(
            self.url + path, method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Authorization": f"Bearer {self.token}",
                     "Content-Type": "application/json", "Accept": accept}),
            timeout=timeout)

    def api(self, method: str, path: str, body=None, timeout: float = 60):
        with self._open(method, path, body, timeout) as resp:
            text = resp.read().decode()
        return json.loads(text) if text else {}

    def deploy(self, name: str, source: str) -> dict:
        """Write the app file and deploy it with the real CLI, from a
        'user' directory, the way the README quickstart does."""
        appdir = f"{self.workdir}/apps/{name}"
        os.makedirs(appdir)
        with open(f"{appdir}/app.py", "w") as f:
            f.write(source)
        proc = subprocess.run(
            [sys.executable, "-m", "tpu9.cli.main", "deploy", "app.py:app",
             "--name", name],
            env=dict(self.env, TPU9_GATEWAY_URL=self.url,
                     TPU9_TOKEN=self.token),
            cwd=appdir, capture_output=True, text=True, timeout=120)
        check(proc.returncode == 0,
              f"tpu9 deploy {name} failed: {proc.stderr.strip()[-1500:]}")
        return json.loads(proc.stdout[proc.stdout.index("{"):])

    def generate(self, name: str, prompt: list, stream: bool = False,
                 timeout: float = 900) -> dict:
        """POST /endpoint/<name>; returns tokens and, for an SSE stream,
        the seconds to the first token event."""
        t0 = time.time()
        body = {"tokens": prompt, "max_new_tokens": NEW_TOKENS}
        if not stream:
            out = self.api("POST", f"/endpoint/{name}", body, timeout)
            return {"tokens": out["tokens"],
                    "seconds": round(time.time() - t0, 3)}
        events, first = [], None
        with self._open("POST", f"/endpoint/{name}", dict(body, stream=True),
                        timeout, accept="text/event-stream") as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("data:"):
                    events.append(json.loads(line[5:]))
                    if first is None and "token" in events[-1]:
                        first = round(time.time() - t0, 3)
        check(events and events[-1].get("done"),
              f"SSE stream ended without a done event: {events[-2:]}")
        streamed = [e["token"] for e in events if "token" in e]
        check(streamed == events[-1]["tokens"],
              "SSE token events disagree with the done event")
        return {"tokens": streamed, "seconds": round(time.time() - t0, 3),
                "first_token_s": first}

    def wait_scaled_to_zero(self, timeout: float = 180) -> float:
        """Until no container is left — the chip has one owner, so the next
        phase must not start before the last one's runner has exited."""
        t0 = time.time()
        while time.time() - t0 < timeout:
            if not self.list_containers():
                return round(time.time() - t0, 1)
            time.sleep(1.0)
        raise PhaseFailed(f"containers still up after {timeout} s: "
                          f"{self.list_containers()}")

    def list_containers(self) -> list:
        states = self.api("GET", "/api/v1/container")
        self.containers.update(c["container_id"] for c in states)
        return states

    def failed_starts(self) -> list[str]:
        """The worker's own record of containers it could not start. A
        replica that comes up on the second try has hidden a fault."""
        return [line.strip() for line in
                self.log_tail("worker", 1 << 20).splitlines()
                if "failed to start" in line or "OOM kill" in line]

    def dump_logs(self) -> None:
        """A failed run's evidence, to stderr and beside the other logs:
        what every runner container printed, and the ends of the gateway's
        and the worker's own logs."""
        if not self.procs:
            return
        import re
        self.containers.update(re.findall(r"ct-[0-9a-f]+",
                                          self.log_tail("worker", 1 << 20)))
        try:
            self.list_containers()
            for cid in sorted(self.containers):
                lines = [e.get("line", "") for e in self.api(
                    "GET", f"/api/v1/container/{cid}/logs")]
                with open(f"{self.workdir}/{cid}.log", "w") as f:
                    f.write("\n".join(lines))
                print(f"--- container {cid} (last lines)\n"
                      + "\n".join(lines[-40:]), file=sys.stderr)
        except (urllib.error.URLError, OSError, ValueError) as exc:
            print(f"--- container logs unavailable: {exc}", file=sys.stderr)
        for which in ("gateway", "worker"):
            print(f"--- {which}.log (end)\n{self.log_tail(which, 3000)}",
                  file=sys.stderr)

    def stop(self) -> None:
        """SIGTERM (the worker tears its containers down), then SIGKILL of
        whatever is left of both process trees."""
        pids = [pid for p in self.procs
                for pid in [p.pid] + _descendants(p.pid)]
        for p in reversed(self.procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 20
        for p in self.procs:
            try:
                p.wait(max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                pass
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


# what a user deploys (README quickstart; examples/02_llama_v5e1.py)
LLM_APP = """\
from tpu9 import QueueDepthAutoscaler, endpoint


def load():
{load}


app = endpoint(tpu={tpu!r}, cpu=4, memory={memory!r}, runner="llm",
               keep_warm_seconds=8, timeout=1500, concurrent_requests=64,
               autoscaler=QueueDepthAutoscaler(max_containers=1),
               env={env!r})(load)
"""

LOAD_PRESET = """\
    from tpu9.serving.presets import load_engine
    return load_engine({preset!r}, max_batch={max_batch},
                       max_seq_len={max_seq_len},
                       prefill_buckets=({chunk},), decode_steps=(1, 8),
                       seed={seed}, compile_ahead=True,
                       topology={topology!r})"""

# the depth-cut comparison model is no preset: its handler builds it here
LOAD_CUT = """\
    import chip_smoke            # the checkout is on the runner's path
    return chip_smoke.cut_engine({args!r})"""

PLAIN_APP = """\
from tpu9 import endpoint


def which(**kwargs):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((256, 256), jnp.bfloat16)
    y = float((x @ x).sum())
    d = jax.devices()
    return {{"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "matmul_sum": y,
            "jax_platforms": jax.config.jax_platforms}}


app = endpoint(tpu="v5e-1", cpu=1, memory={memory!r}, keep_warm_seconds=2,
               timeout=300, env={env!r})(which)
"""

# Host memory of a TPU container: a process that has initialised the TPU
# backend sits at ~13 GB of host RSS on one chip before it has done anything
# (PR 21: a 4Gi container was OOM-killed at 13,176 MB). Four chips get four
# times the room.
MEMORY = {"v5e-1": "24Gi", "v5e-4": "96Gi"}


def cache_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path))


def make_requests(seed: int, vocab: int, chunk: int) -> list[dict]:
    """The request mix: short; several prefill chunks (a fused group of
    four plus single-chunk tails); an SSE stream; a pair sharing a prefix
    of more than two KV blocks; one more short so five are in flight."""
    rng = random.Random(seed)

    def toks(n):
        return [rng.randrange(3, vocab) for _ in range(n)]

    shared = toks(2 * chunk + chunk // 3)
    return [
        {"name": "short", "prompt": toks(12)},
        {"name": "multi_chunk", "prompt": toks(5 * chunk + chunk // 3)},
        {"name": "stream", "prompt": toks(40), "stream": True},
        {"name": "prefix_a", "prompt": shared + toks(20)},
        {"name": "short_2", "prompt": toks(25)},
        {"name": "prefix_b", "prompt": shared + toks(20), "after": True},
    ]


@dataclasses.dataclass
class Run:
    """What every phase of one run shares."""
    args: argparse.Namespace
    stack: Stack
    device: dict            # what the probe child saw
    on_tpu: bool
    env: dict               # environment of children, gateway and worker
    container_env: dict     # rides every deployment
    cache_dir: str
    model: dict             # widths of the preset, from the `widths` child

    def child(self, name: str, args: dict) -> dict:
        return run_child(name, args, self.env)

    def requests(self, seed_offset: int = 0) -> list[dict]:
        return make_requests(self.args.seed + seed_offset,
                             self.model["vocab_size"], self.args.chunk)


def serve_requests(stack: Stack, name: str, reqs: list[dict]) -> dict:
    """First wave together (>= 4 in flight: continuous batching), then the
    second of the shared-prefix pair once its twin's blocks are cached."""
    def one(r):
        try:
            r.update(stack.generate(name, r["prompt"],
                                    r.get("stream", False)))
        except (urllib.error.URLError, OSError, KeyError,
                PhaseFailed) as exc:
            r["error"] = f"{type(exc).__name__}: {exc}"

    first_wave = [r for r in reqs if not r.get("after")]
    with concurrent.futures.ThreadPoolExecutor(len(first_wave)) as pool:
        list(pool.map(one, first_wave))
    for r in reqs:
        if r.get("after"):
            one(r)
    errors = {r["name"]: r["error"] for r in reqs if "error" in r}
    check(not errors, f"{len(errors)} of {len(reqs)} requests failed: "
                      f"{errors}")
    for r in reqs:
        check(len(r["tokens"]) == NEW_TOKENS,
              f"{r['name']}: {len(r['tokens'])} tokens, not {NEW_TOKENS}")
    return {"sent": len(reqs), "succeeded": len(reqs) - len(errors),
            "failed": len(errors), "in_flight_together": len(first_wave),
            "request_seconds": {r["name"]: r["seconds"] for r in reqs},
            "first_token_s": next(r["first_token_s"] for r in reqs
                                  if r.get("stream"))}


def bring_up(stack: Stack, name: str, warm_prompt: list) -> dict:
    """Scale from zero with one request, then read what the RUNNER says
    about itself on ``/health``: its device and its bring-up seconds."""
    t0 = time.time()
    stack.generate(name, warm_prompt, timeout=BRINGUP_TIMEOUT_S)
    wall = round(time.time() - t0, 1)
    stack.list_containers()
    h = stack.api("GET", f"/endpoint/{name}/health")
    return {"device": {"platform": h["device_platform"],
                       "kind": h["device_kind"],
                       "count": h["device_count"]},
            "bringup_request_s": wall,
            "bringup_seconds": {k[len("coldstart_"):]: v
                                for k, v in h.items()
                                if k.startswith("coldstart_")
                                and isinstance(v, (int, float))},
            "health": h}


def serve_phase(run: Run, phase: str, name: str, engine_args: dict,
                tpu: str, reqs: list[dict], want_count: int = 1) -> dict:
    """Deploy, bring up, serve ``reqs``, check the runner's own report, and
    wait for the replica to scale to zero (releasing the chip)."""
    stack = run.stack
    before = cache_entries(run.cache_dir)
    load = LOAD_CUT.format(args=engine_args) if engine_args["layers"] \
        else LOAD_PRESET.format(**engine_args)
    stack.deploy(name, LLM_APP.format(load=load, tpu=tpu,
                                      memory=MEMORY[tpu],
                                      env=run.container_env))
    up = bring_up(stack, name, reqs[0]["prompt"])
    at_bind = up.pop("health")
    served = serve_requests(stack, name, reqs)
    h = stack.api("GET", f"/endpoint/{name}/health")
    line = dict(
        up, attention_decode=at_bind["attention_decode"],
        graph_kernels=at_bind["graph_kernels"],
        hbm_used_gb_by_chip=at_bind["hbm_used_gb_by_chip"],
        hbm_peak_gb_per_chip=h["hbm_peak_gb_per_chip"],
        hbm_predicted_gb_per_chip=h["hbm_predicted_gb_per_chip"],
        compile_cache={"dir": run.cache_dir, "entries_before": before,
                       "entries_after": cache_entries(run.cache_dir)},
        requests=served,
        prefix_cache={k: h["prefix_cache"].get(k) for k in
                      ("hits", "misses")},
        graph_compiles_post_warmup=h["graph_compiles_post_warmup"],
        tokens_generated=h["tokens_generated"],
        failed_starts=stack.failed_starts())
    emit(phase, ok=True, **line)
    check(not line["failed_starts"],
          f"the worker lost containers on the way: {line['failed_starts']}")
    # the runner's device is the one the probe saw (a CPU rehearsal's fake
    # chips put the runner on the CPU backend)
    if run.on_tpu:
        check(line["device"] == dict(run.device, count=want_count),
              f"runner's engine is on {line['device']}, the probe saw "
              f"{run.device} and the engine should span {want_count}")
        check(line["attention_decode"].startswith("pallas"),
              f"decode attention is {line['attention_decode']!r}")
        layers = engine_args["layers"] or run.model["n_layers"]
        for graph, calls in line["graph_kernels"].items():
            check(calls == layers or not graph.startswith("decode"),
                  f"compiled {graph} holds {calls} pallas calls, the model "
                  f"has {layers} layers")
    check(h["graph_compiles_post_warmup"] == 0,
          f"{h['graph_compiles_post_warmup']} compilations after warm-up")
    check((line["prefix_cache"]["hits"] or 0) >= 1,
          f"shared-prefix pair produced no prefix-cache hit: "
          f"{h['prefix_cache']}")
    stack.wait_scaled_to_zero()
    return line


def reference_phase(run: Run, phase: str, engine_args: dict,
                    reqs: list[dict]) -> None:
    ref = run.child("reference", dict(
        engine_args, requests=[{"name": r["name"], "prompt": r["prompt"],
                                "tokens": r["tokens"]} for r in reqs]))
    ok = ref["worst_margin"] <= TOL_LOGIT
    emit(phase, ok=ok, tolerance=TOL_LOGIT, **ref)
    check(ok, f"served token {ref['worst_at']} is {ref['worst_margin']} "
              f"below the reference maximum (tolerance {TOL_LOGIT})")


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def run_one_chip(run: Run) -> None:
    a, stack, m = run.args, run.stack, run.model
    kern = run.child("kernels", dict(
        batch=a.max_batch, q_heads=m["n_heads"], kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], block=a.chunk, context=a.max_seq_len,
        seed=a.seed))
    worst = max(kern["diffs"].values())
    emit("kernels", ok=worst <= TOL_KERNEL, tolerance=TOL_KERNEL, **kern)
    check(worst <= TOL_KERNEL, f"kernel vs oracle max-abs-diff {worst}")
    if run.on_tpu:
        check(not kern["interpret"] and all(kern["tpu_custom_call"].values()),
              f"a kernel did not compile to a pallas call: {kern}")

    emit("stack", ok=True, **stack.start())

    engine_args = dict(preset=a.preset, layers=0, topology="1x1",
                       max_batch=a.max_batch, max_seq_len=a.max_seq_len,
                       chunk=a.chunk, seed=a.seed)
    reqs = run.requests()
    first = serve_phase(run, "serve", "smoke-llm", engine_args, "v5e-1", reqs)
    reference_phase(run, "reference", engine_args, reqs)

    stack.deploy("smoke-plain", PLAIN_APP.format(memory=MEMORY["v5e-1"],
                                                 env=run.container_env))
    t0 = time.time()
    plain = stack.api("POST", "/endpoint/smoke-plain", {}, timeout=300)
    ok = plain.get("platform") == run.device["platform"]
    emit("plain_endpoint", ok=ok, seconds=round(time.time() - t0, 1),
         **plain)
    check(ok, f"plain @endpoint(tpu='v5e-1') answered {plain}")
    stack.wait_scaled_to_zero()

    # warm restart: the same deployment scales from zero again
    before = cache_entries(run.cache_dir)
    up = bring_up(stack, "smoke-llm", reqs[0]["prompt"])
    del up["health"]
    after = cache_entries(run.cache_dir)
    emit("warm_restart", ok=after == before, **up,
         compile_cache={"dir": run.cache_dir, "entries_before": before,
                        "entries_after": after},
         first_bringup_request_s=first["bringup_request_s"],
         first_bringup_seconds=first["bringup_seconds"])
    check(after == before, f"the second bring-up added {after - before} "
                           "compile-cache entries")


def run_four_chips(run: Run) -> None:
    a, m = run.args, run.model
    emit("stack", ok=True, **run.stack.start())
    sizes = dict(preset=a.preset, max_batch=a.max_batch,
                 max_seq_len=a.max_seq_len, chunk=a.chunk, seed=a.seed)
    # tp must divide the kv heads (the tiny rehearsal model has two)
    mesh = "4x1" if m["n_kv_heads"] % 4 == 0 else "tp=2,fsdp=2"
    cut = a.layers or m["n_layers"] // 2

    # 1. the comparison: depth cut so the model also fits ONE chip, served
    # 1x1 and on the mesh, both held to the same single-chip reference
    runs = {}
    for tag, topo, count in (("1x1", "1x1", 1), ("mesh", mesh, 4)):
        reqs = run.requests()
        serve_phase(run, f"serve_cut_{tag}", f"smoke-cut-{tag}",
                    dict(sizes, layers=cut, topology=topo), "v5e-4", reqs,
                    want_count=count)
        runs[tag] = [dict(r, name=f"{tag}:{r['name']}") for r in reqs]
    same = sum(x["tokens"] == y["tokens"]
               for x, y in zip(runs["1x1"], runs["mesh"]))
    emit("compare_cut", ok=True, layers=cut, mesh=mesh,
         requests_token_identical=same, requests=len(runs["mesh"]))
    reference_phase(run, "reference_cut",
                    dict(sizes, layers=cut, topology="1x1"),
                    runs["1x1"] + runs["mesh"])

    # 2. full depth on the mesh: weights that fit no single chip
    full = dict(sizes, layers=0, topology=mesh)
    reqs = run.requests(seed_offset=1)
    line = serve_phase(run, "serve_full_mesh", "smoke-full-mesh", full,
                       "v5e-4", reqs, want_count=4)
    by_chip = line["hbm_used_gb_by_chip"]
    if run.on_tpu:
        check(len(by_chip) == 4 and max(by_chip) <= 1.1 * min(by_chip),
              f"per-chip HBM after bind is not even: {by_chip}")
    reference_phase(run, "reference_full_mesh", full, reqs)
    coll = run.child("collectives", full)
    emit("collectives", ok=bool(coll["collectives"]), **coll)
    check(coll["collectives"], "the mesh decode step holds no collective")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--preset", default="")
    ap.add_argument("--layers", type=int, default=0,
                    help="--chips 4: depth of the cut comparison model")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=128,
                    help="prefill chunk = KV block size")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: walk every phase on the CPU backend "
                         "anyway, then exit 3")
    ap.add_argument("--child", nargs=2, metavar=("NAME", "JSON"),
                    help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child:
        print(json.dumps(CHILDREN[a.child[0]](json.loads(a.child[1]))))
        return 0
    a.preset = a.preset or ("llama3-8b-int8" if a.chips == 1
                            else "llama3-8b")

    env = dict(os.environ, PYTHONPATH=ROOT, TPU_LOG_DIR="disabled")
    if a.rehearse and a.chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={a.chips}").strip()
    device = run_child("probe", {}, env)
    on_tpu = device["platform"] == "tpu"
    emit("probe", ok=on_tpu, **device)
    if not on_tpu and not a.rehearse:
        print("chip_smoke: no TPU here — jax reports "
              f"{device}", file=sys.stderr)
        return 1
    check(device["count"] == a.chips or not on_tpu,
          f"--chips {a.chips} but jax sees {device['count']} devices")

    from tpu9.utils.paths import compile_cache_dir
    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    # the one cache rule is forwarded by the worker; these two make every
    # program land in the cache, so "no new entries" is exact, not a
    # matter of which compile happened to take a second
    container_env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                     "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    if not on_tpu and a.chips > 1:
        container_env["XLA_FLAGS"] = env["XLA_FLAGS"]
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.update(container_env)

    workdir = os.path.join(ROOT, ".cache", "chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    emit("setup", ok=True, workdir=workdir, compile_cache_dir=cache_dir,
         compile_cache_entries=cache_entries(cache_dir),
         native="not built, not needed: the process runtime runs the "
                "endpoint without a native binary")
    run = Run(args=a, stack=Stack(workdir, env, a.chips,
                                  fake_chips=not on_tpu),
              device=device, on_tpu=on_tpu, env=env,
              container_env=container_env, cache_dir=cache_dir,
              # resolved in a child pinned to the CPU: importing the model
              # code imports jax, which this parent never does
              model=run_child("widths", {"preset": a.preset},
                              dict(env, JAX_PLATFORMS="cpu")))
    try:
        (run_one_chip if a.chips == 1 else run_four_chips)(run)
        check(not run.stack.failed_starts(),
              f"the worker lost containers: {run.stack.failed_starts()}")
    except BaseException:
        run.stack.dump_logs()
        raise
    finally:
        run.stack.stop()
    assert "jax" not in sys.modules, "the parent must stay off jax"
    if not on_tpu:
        print("chip_smoke: rehearsal complete on "
              f"{device['platform']} — not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as exc:
        emit("failed", ok=False, error=str(exc))
        sys.exit(1)
