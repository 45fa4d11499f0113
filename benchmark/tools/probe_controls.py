#!/usr/bin/env python3
"""Builder's tool: where a configuration's ``correct_tolerance_logit`` comes
from, for a reference that carries its own controls. The engine of a
configuration at its real size in THIS process (no gateway), the benchmark's
six probes served for each seed and held to the reference by the harness's
own comparison (``correctness.probe_margins``, judged as ``run.py`` judges
it: worst margin <= tolerance) — and, on the same served tokens, the
reference run once more under each ``--control KEY=JSON``: an override of
the reference's ``model`` dict that has to come out NOT correct (for
``reference/eva.py``: ``skip_summaries=true``, ``int8_weights=true``; the
rounding happens inside the reference, so no second copy of the weights is
held). ``tools/probe_sweep.py`` is the older form, with its controls built
in.

One JSON line a seed and control, the device's memory beside them. A seed
with one control takes about 31 s at evabyte-6.5b-l16's size (builder, PR 46:
40 seeds 1,241 s).

    chiprun -- python3 benchmark/tools/probe_controls.py \\
        --config evabyte-6.5b-l16 --seeds 1,2,3 \\
        --control skip_summaries=true --control int8_weights=true
"""

from __future__ import annotations

import argparse
import asyncio
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
    ap.add_argument("--control", action="append", default=[],
                    metavar="KEY=JSON")
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

    controls = []
    for item in args.control:
        key, _, value = item.partition("=")
        controls.append((key, json.loads(value)))
    config = manifest.load_config(manifest.load(), args.config)
    family = manifest.family(config)
    model = family.model_sizes(config)
    cfg = family.program_config(model)
    knobs, tol = config["engine"], config["correct_tolerance_logit"]
    policy = make_policy(knobs["topology"])
    t0 = time.time()
    engine = InferenceEngine(abstract_params_for(cfg, False), cfg,
                             serve.engine_config(knobs), policy=policy)
    timings = engine.precompile()
    say(precompile_s=round(time.time() - t0, 1), timings=timings)

    def judged(sizes, probes):
        out = correctness.probe_margins(engine.params, sizes, probes,
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
            say(seed=seed, tolerance=tol, **judged(model, probes),
                post_warmup_compiles=engine.stats()[
                    "graph_compiles_post_warmup"])
            for key, value in controls:
                say(seed=seed, control={key: value},
                    **judged(dict(model, **{key: value}), probes))
        await engine.stop()
        mem("end")

    asyncio.run(sweep())


if __name__ == "__main__":
    main()
