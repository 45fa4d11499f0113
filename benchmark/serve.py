"""What runs INSIDE the runner container: the deployed handler calls
:func:`build_engine`, which turns a configuration file into the program's
model config (through the configuration's family, ``manifest.family``) and
``InferenceEngine`` — weights made on the device from the seed in one jitted
call — and starts the benchmark's mailbox thread.

The mailbox is how the harness reaches the one process that holds the chip
without a second process (a chip belongs to one process at a time, and a new
one takes ~15 s to reach it): the harness drops ``<op>.request.json`` into the
run directory, the thread answers with ``<op>.result.json``.

- ``reference``: the plain float32 reference over the probes, on the weights
  the engine serves (and, on a mesh, on the same mesh), while the engine idles
- ``memory``: ``peak_bytes_in_use`` of every device of the engine
- ``trace``: a profiler trace of a stated number of decode steps, or of a
  stated number of seconds where those come first, started and stopped here
  (:func:`bounded_trace`). The runner's own ``POST /profile`` counts main-loop
  decode windows only, so a few armed windows of an open loop traced 15-35 s
  and writing that out held the run for minutes (PR 23); only the process
  that holds the chip can trace it, and this thread is in it.
"""

from __future__ import annotations

import json
import os
import threading
import time

from benchmark import manifest


def model_sizes(config: dict) -> dict:
    """Only for ``tests/test_chip_compile.py``, the program's own test, which
    lies outside the benchmark's paths and still calls this and
    :func:`decoder_config`: the family's sizes, with the family's name beside
    them so that the second call finds the family again. The harness goes
    through ``manifest.family`` and calls neither."""
    return dict(manifest.family(config).model_sizes(config),
                family=config["family"])


def decoder_config(model: dict):
    return manifest.family(model).program_config(model)


def engine_config(knobs: dict):
    from tpu9.serving import EngineConfig
    return EngineConfig(
        max_batch=knobs["max_batch"], max_seq_len=knobs["max_seq_len"],
        prefill_buckets=(knobs["prefill_chunk"],),
        decode_steps=tuple(knobs["decode_steps"]),
        kv_block_size=knobs["kv_block_size"],
        kv_pool_blocks=knobs["kv_pool_blocks"],
        prefill_chunk=knobs["prefill_chunk"],
        prefix_cache_blocks=knobs["prefix_cache_blocks"],
        admit_group_chunks=knobs["admit_group_chunks"])


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_params(cfg, policy, seed: int):
    """Weights on the device from the seed in ONE jitted call, in the type
    they are served in, each leaf built where the policy shards it."""
    import jax

    from tpu9.models import init_decoder

    def init(rng):
        return init_decoder(rng, cfg)

    if policy.mesh is None:
        return jax.jit(init)(seed_key(seed))
    return policy.build_params(init, seed_key(seed))


def build_engine(args: dict):
    """``args``: ``config`` (the configuration as run, overrides applied),
    ``seed``, ``run_dir``."""
    t0 = time.monotonic()
    import jax
    devices = jax.devices()
    opened = time.monotonic()

    from tpu9.serving import InferenceEngine
    from tpu9.serving.shard import make_policy
    config = args["config"]
    family = manifest.family(config)
    model = family.model_sizes(config)
    cfg = family.program_config(model)
    policy = make_policy(config["engine"]["topology"])
    # as load_engine(compile_ahead=True) does for a preset: the engine is
    # built on the ABSTRACT weights and its programs compile (or load from
    # the cache) on the host while the device makes the weights
    built: dict = {}

    def make_weights():
        built["params"] = jax.block_until_ready(
            build_params(cfg, policy, args["seed"]))
        built["loaded"] = time.monotonic()

    weights = threading.Thread(target=make_weights, name="benchmark-weights")
    weights.start()
    from tpu9.serving.presets import abstract_params_for
    engine = InferenceEngine(abstract_params_for(cfg, False), cfg,
                             engine_config(config["engine"]), policy=policy)
    timings = engine.precompile()
    compiled = time.monotonic()
    weights.join()
    if "params" not in built:
        raise RuntimeError("building the weights failed (see the log above)")
    engine.bind_params(built["params"])
    # the benchmark's own spans around the calls into each layer; the runner
    # adds handler_s / warmup_s / ready_s and /health shows all as coldstart_*
    engine.bringup = {"device_open_s": round(opened - t0, 4),
                      "load_s": round(built["loaded"] - opened, 4),
                      "compile_ahead_s": round(compiled - opened, 4),
                      **timings}
    threading.Thread(target=_mailbox, name="benchmark-mailbox", daemon=True,
                     args=(args["run_dir"], engine, model, config,
                           len(devices))).start()
    return engine


TRACE_POLL_S = 0.02


def bounded_trace(profiler, steps_now, directory: str, seconds: float,
                  steps=None, **start_options) -> dict:
    """Trace until ``steps`` more decode steps have been dispatched
    (``steps_now()`` is the engine's counter) or ``seconds`` have passed,
    whichever comes first. What stopping a trace and reducing it cost
    follows the number of traced events, not the seconds (PR 34), and a
    closed loop with a faster step puts more steps into the same seconds:
    bounded by steps, a traced run takes as long on a faster program."""
    t0 = time.monotonic()
    profiler.start_trace(directory, **start_options)
    started = time.monotonic()
    first = steps_now()
    while True:
        left = started + seconds - time.monotonic()
        if left <= 0 or (steps and steps_now() - first >= steps):
            break
        time.sleep(min(TRACE_POLL_S, left))
    asked = time.monotonic()
    traced_steps = steps_now() - first
    profiler.stop_trace()
    return {"start_s": round(started - t0, 3),
            "traced_s": round(asked - started, 3),
            "traced_steps": traced_steps,
            "stop_s": round(time.monotonic() - asked, 3)}


def _answer(run_dir: str, op: str, fn) -> None:
    req = os.path.join(run_dir, f"{op}.request.json")
    if not os.path.exists(req):
        return
    with open(req) as f:
        payload = json.load(f)
    os.remove(req)
    try:
        out = fn(payload)
    except Exception as exc:    # noqa: BLE001 — the harness reads the error
        out = {"error": f"{type(exc).__name__}: {exc}"}
    tmp = os.path.join(run_dir, f"{op}.result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(run_dir, f"{op}.result.json"))


def _mailbox(run_dir, engine, model, config, n_devices) -> None:
    def reference(payload):
        from benchmark.correctness import probe_margins
        t0 = time.monotonic()
        out = probe_margins(engine.params, model, payload["probes"],
                            config["reference"])
        out["seconds"] = round(time.monotonic() - t0, 3)
        return out

    def trace(payload):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # millions of host events otherwise
        options.host_tracer_level = 1
        # the counter itself, not ``engine.stats()``: that sweeps every
        # chip's memory statistics and the latency rings, fifty times a
        # second beside the serve loop it would slow what is being traced
        return bounded_trace(
            jax.profiler, lambda: engine._stats["decode_steps"],
            payload["dir"], float(payload["seconds"]), payload.get("steps"),
            profiler_options=options)

    def memory(_payload):
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in engine._devices]
        return {"peak_bytes_by_device": peaks, "devices_visible": n_devices}

    while True:
        try:
            _answer(run_dir, "reference", reference)
            _answer(run_dir, "trace", trace)
            _answer(run_dir, "memory", memory)
        except OSError:
            pass
        time.sleep(0.05)
