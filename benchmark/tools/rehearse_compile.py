#!/usr/bin/env python3
"""Builder's tool: compile the serving programs of a configuration at its
real sizes for a DESCRIBED v5e:2x2 — no chip attached — and print what the
TPU compiler says each program holds per chip (``memory_analysis()``).

    python3 benchmark/tools/rehearse_compile.py [configuration ...]

What the compiler refuses here costs no chip time. It counts one program at a
time, not what else the process keeps on the device. No TPU is attached, so
the program's attention dispatchers would take their XLA paths: this tool
steers them to the pallas kernels the chip runs (``on_tpu`` patched in the
tool, not in the program); ``--xla-attention`` leaves them alone. A compile
that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import manifest, serve  # noqa: E402


def main() -> int:
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard.plan import Topology, parse_topology
    from tpu9.serving.shard.policy import MeshPolicy
    if "--xla-attention" not in sys.argv:
        import tpu9.ops.attention as attention_ops
        attention_ops.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    m = manifest.load()
    names = [a for a in sys.argv[1:] if not a.startswith("--")] \
        or [c["name"] for c in m["configs"]]
    for name in names:
        config = manifest.load_config(m, name)
        family = manifest.family(config)
        cfg = family.program_config(family.model_sizes(config))
        ecfg = serve.engine_config(config["engine"])
        t = parse_topology(config["engine"]["topology"]) or Topology(1, 1)
        policy = MeshPolicy(t, devices=topo.devices[:t.n_chips])
        graphs = GraphFactory(cfg, ecfg, policy, chunk=ecfg.prefill_chunk)
        st = abstract_state(cfg, ecfg, policy)
        params = abstract_params_for(cfg, False)
        weights = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(params))
        print(json.dumps({"configuration": name, "chips": t.n_chips,
                          "weights_gb_total": round(weights / 1e9, 3)}),
              flush=True)
        for key, fn, args in graphs.lowering_jobs(
                params, st["kv_cache"], st["pool"], st["scratch"], st["mb"],
                [ecfg.prefill_chunk], (), st["rng"]):
            t0 = time.time()
            try:
                compiled = fn.lower(*args).compile()
            except Exception as exc:    # noqa: BLE001 — the compiler's word
                print(json.dumps({"program": str(key), "refused":
                                  f"{type(exc).__name__}: {exc}"[:600]}),
                      flush=True)
                continue
            ma = compiled.memory_analysis()
            gb = {k: round(getattr(ma, f"{k}_size_in_bytes") / 1e9, 3)
                  for k in ("argument", "output", "temp", "alias")}
            gb["live_peak"] = round(gb["argument"] + gb["output"] + gb["temp"]
                                    - gb["alias"], 3)
            print(json.dumps({"program": str(key), "per_chip_gb": gb,
                              "compile_s": round(time.time() - t0, 1)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
