#!/usr/bin/env python3
"""Builder's tool: a traced run's time to first token as a waterfall, hop by
hop, from the ``context.json`` the run left in its directory and the readers
of THIS checkout: the client's mean over the window's records, the nine
per-layer metrics that name its parts from the load generator down to the
engine and back, what they leave unnamed, and the checks that the parts are of
the same requests and were stamped where they say.

    python3 benchmark/tools/ttft.py [run_dir ...]   (default: the newest)

Prints one JSON object a run. ``waterfall_ms`` is in the order a request
passes the hops; ``unnamed_ms`` is the client's mean less their sum (the step
from the enqueue to the runner's headers, counted in ``runner_ingest_ms`` and
again in ``engine_queue_wait_ms``, comes out negative here; anything else is
a hop nobody stamped). ``check``: ``runner_first_ms`` against ``ttft`` +
``stream_lag``, both headers-or-enqueue -> first token written on the
runner's clock. ``decode_window_ms``: what the serve loop holds the runner's
event loop for, the yardstick of ``runner_door_ms``; ``connect_s_at_end``: the
percentiles of send -> headers back, which the door dominates.
"""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, metrics, readers  # noqa: E402

# the order a streamed request passes them, out and back
HOPS = ("client_hop_ms", "gateway_pre_forward_ms", "runner_door_ms",
        "runner_ingest_ms", "engine_queue_wait_ms", "engine_admit_ms",
        "first_token_hold_ms", "stream_lag_ms", "gateway_first_relay_ms")
SUMMARIES = ("ingest", "runner_first", "queue_wait", "prefill", "first_hold",
             "ttft", "stream_lag")


def waterfall(ctx: dict) -> dict:
    parts = {name: manifest.layer_reader(name).read(ctx) for name in HOPS}
    named = [v for v in parts.values() if metrics.finite(v)]
    firsts = manifest.layer_reader("client_hop_ms").ttfts_ms(ctx)
    client = sum(firsts) / len(firsts) if firsts else None
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    ttft = readers.engine_phase_mean_ms(ctx, "ttft")
    lag = readers.engine_phase_mean_ms(ctx, "stream_lag")
    return {
        "cell": ctx["cell"], "client_ttft_mean_ms": client,
        "waterfall_ms": parts, "named_ms": sum(named),
        "unnamed_ms": client - sum(named)
        if client is not None and len(named) == len(HOPS) else None,
        "check": {"runner_first_ms":
                  readers.engine_phase_mean_ms(ctx, "runner_first"),
                  "ttft_plus_stream_lag_ms":
                  ttft + lag if None not in (ttft, lag) else None},
        "observations": {
            "records_with_a_first_token": len(firsts),
            **{name: gw.observations(ctx, name)
               for name in (gw.PRE, gw.CONNECT, gw.FIRST)},
            **{f"latency.{part}":
               readers.nested_delta(ctx, "latency", f"{part}_count")
               for part in SUMMARIES}},
        "max_batch": ctx["engine"]["max_batch"],
        "decode_window_ms": readers.engine_phase_mean_ms(ctx, "decode_window"),
        # the door's distribution: the gateway's summary as it stood at the
        # window's end (cumulative since the gateway started: set-up too)
        "connect_s_at_end": (ctx["gateway1"].get("summaries") or {}).get(
            gw.CONNECT),
    }


def main() -> int:
    dirs = sys.argv[1:] or sorted(
        glob.glob(os.path.join(ROOT, "benchmark", "out", "*.trace1")),
        key=os.path.getmtime)[-1:]
    for run_dir in dirs:
        with open(os.path.join(run_dir, "context.json")) as f:
            ctx = json.load(f)
        print(json.dumps({"run": os.path.basename(run_dir), **waterfall(ctx)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
