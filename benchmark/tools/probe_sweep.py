#!/usr/bin/env python3
"""Builder's tool: where a configuration's ``correct_tolerance_logit`` comes
from. The engine of a configuration at its real size in THIS process (no
gateway), the benchmark's six probes served for each seed and held to the
reference by the harness's own comparison (``correctness.probe_margins``,
judged as ``run.py`` judges it: worst margin <= tolerance) — and, on the
same served tokens, the controls that have to come out NOT correct:

- ``--fewer-passes``: the reference with one pass less (a family with
  ``total_ut_steps``): the comparison sees the mechanism
- ``--int8-weights``: the reference on weights rounded to int8 and back, the
  nearest precision below the bf16 a configuration states (a second copy of
  the layers on the device: give ``--blocks`` a pool small enough)
- ``--kv-quant int8``: the ENGINE with an int8 KV pool, held to the reference

One JSON line a seed, the device's memory after each phase beside them.

    chiprun -- python3 benchmark/tools/probe_sweep.py --config ouro-2.6b \\
        --seeds 1,2,3 --fewer-passes --int8-weights
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--blocks", type=int, default=0,
                    help="another kv_pool_blocks than the configuration's")
    ap.add_argument("--kv-quant", default="")
    ap.add_argument("--fewer-passes", action="store_true")
    ap.add_argument("--int8-weights", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark import correctness, manifest, serve
    from tpu9.serving import InferenceEngine
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard import make_policy
    run = manifest.module("run")

    def say(**line):
        print(json.dumps(line), flush=True)

    def mem(tag):
        st = jax.devices()[0].memory_stats() or {}
        say(mem=tag, **{k: round(st.get(k, 0) / 1e9, 3) for k in
                        ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})

    config = manifest.load_config(manifest.load(), args.config)
    if args.blocks:
        config["engine"]["kv_pool_blocks"] = args.blocks
    family = manifest.family(config)
    model = family.model_sizes(config)
    cfg = family.program_config(model)
    knobs, tol = config["engine"], config["correct_tolerance_logit"]
    policy = make_policy(knobs["topology"])
    ecfg = serve.engine_config(knobs)
    if args.kv_quant:
        ecfg = dataclasses.replace(ecfg, kv_quant=args.kv_quant)
    t0 = time.time()
    engine = InferenceEngine(abstract_params_for(cfg, False), cfg, ecfg,
                             policy=policy)
    timings = engine.precompile()
    say(precompile_s=round(time.time() - t0, 1), timings=timings)
    mem("precompiled")

    def judged(params, sizes, probes):
        out = correctness.probe_margins(params, sizes, probes,
                                        config["reference"])
        return {"worst_margin": out["worst_margin"],
                "worst_at": out["worst_at"],
                "correct": bool(out["worst_margin"] <= tol)}

    async def sweep():
        started = False
        for seed in (int(s) for s in args.seeds.split(",")):
            engine.params = None       # free the last seed's weights first
            engine.bind_params(jax.block_until_ready(
                serve.build_params(cfg, policy, seed)))
            if not started:
                engine.warmup()
                await engine.start()
                started = True
                mem("warm")
            probes = run.make_probes(
                np.random.default_rng(seed ^ 0x5EED), model["vocab_size"],
                knobs["prefill_chunk"], knobs["max_seq_len"])
            for p in probes:
                p["tokens"] = await engine.generate(
                    p["prompt"], max_new_tokens=run.PROBE_TOKENS)
            st = engine.stats()
            line = {"seed": seed, "kv_quant": args.kv_quant,
                    "tolerance": tol,
                    **judged(engine.params, model, probes),
                    **{k: st[k] for k in (
                        "loop_tokens", "loop_passes", "loop_exit_hist",
                        "graph_compiles_post_warmup") if k in st}}
            say(**line)
            line = {"seed": seed, "controls": True}
            if args.fewer_passes:
                fewer = dict(model,
                             total_ut_steps=model["total_ut_steps"] - 1)
                line["one_pass_less"] = judged(engine.params, fewer, probes)
            if args.int8_weights:
                from tpu9.ops.quant import (dequantize_weight,
                                            quantize_weight)
                rounded = jax.jit(lambda w: dequantize_weight(
                    quantize_weight(w), w.dtype))
                # every matrix of the layers; embedding and head stay
                q = dict(engine.params, layers=jax.tree_util.tree_map(
                    lambda x: rounded(x) if x.ndim == 2 else x,
                    engine.params["layers"]))
                line["int8_weights"] = judged(q, model, probes)
                del q
            if len(line) > 2:
                say(**line)
        await engine.stop()
        mem("end")

    asyncio.run(sweep())


if __name__ == "__main__":
    main()
