#!/usr/bin/env python3
"""Builder's tool: where a token's gap goes, from a traced run's directory —
the sibling of ``tools/ttft.py`` for the judged metric. From ``context.json``
and the readers of THIS checkout:

- ``waterfall_ms``: the mean gap between two tokens of one stream at each hop
  that stamps it — the client's records, the gateway's
  ``tpu9_gateway_stream_gap_s``, the runner's ``latency.runner_gap``, the
  engine's ``latency.tpot`` — every one (last - first) / (tokens - 1), a mean
  over requests; ``hops_ms`` is what each hop adds to the one inside it.
- ``engine_gap_ms``: the engine's gap over the window's delivered tokens
  (``gap_lane_period_s`` / ``gap_tokens``: weighted by tokens, where
  ``latency.tpot`` is a mean over requests) = ``decode_ms``
  (``decode_period_ms`` x the lanes' steps a token) + ``tpot_admit_stall_ms``
  (what the windows an admission touched cost their lanes beyond that step),
  with ``admit_episode_ms`` (``gap_lane_admit_s`` a token: the whole
  admission episodes between a lane's deliveries, the decode windows
  interleaved inside them included), ``stream_gap_max_ms`` and
  ``prefill_pad_share`` beside them.
- ``admission``: the serve loop's own admission phases (``host_phase_s``)
  over the window, and what it admitted.

And from the trace, where the directory holds one (``check``): over the
fan-outs whose whole period lies inside the traced span, the sum of their
admit parts against chip 0's time inside the same admission episodes — the
prefill programs (``readers.PREFILL_PROGRAMS``, ``jit_lane_splice``), the
decode runs interleaved inside them, the chip's idle time under
``engine.admit*`` / ``engine.first_sync`` / ``engine.deliver_first``, and
whatever else ran there — the clean windows' period a step against the
trace's ``decode_step_ms``, with the decode runs' durations summed beside the
time they cover (runs whose events overlap read a longer step than the
streams saw) — and the split ``tpot_admit_stall_ms`` makes: what the other
fan-outs' periods hold beyond that step for each of their steps
(``stall_ms``) against the episodes' chip time that is NOT a decode run
(``stall_covered_pct``).

    python3 benchmark/tools/tpot.py [run_dir ...]   (default: the newest)

Prints one JSON object a run. A program without the counters (an older
commit) prints the client's gap and nulls.
"""

from __future__ import annotations

import glob
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"     # reading a trace opens no chip
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import host_phases, manifest, metrics, readers, trace  # noqa: E402

GATEWAY_GAP = "tpu9_gateway_stream_gap_s"
LAYER = ("engine_tpot_ms", "tpot_admit_stall_ms", "decode_period_ms",
         "stream_gap_max_ms", "tpot_relay_ms", "prefill_pad_share")
# the serve loop's phases an admission episode is made of (its interleaved
# dispatches and yields lie inside it too)
ADMIT_PHASES = host_phases.GROUPS["admit"] + ("engine.first_sync",)
ADMIT_PROGRAMS = readers.PREFILL_PROGRAMS + ("jit_lane_splice",)
FANOUT, ADMIT, DELIVER = ("engine.window.fanout", "engine.admit",
                          "engine.deliver_first")


def client_gaps_ms(ctx: dict) -> list:
    """(last - first token) / (tokens - 1) of every record of the window
    with two tokens or more, judged or not: the summaries hold all."""
    return [(r["token_s"][-1] - r["token_s"][0]) / (len(r["token_s"]) - 1)
            * 1e3 for r in ctx["records"]
            if r["due_s"] is not None and len(r["token_s"]) >= 2]


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def _less(a, b):
    return a - b if None not in (a, b) else None


def waterfall(ctx: dict) -> dict:
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    stall = manifest.layer_reader("tpot_admit_stall_ms")
    layer = {name: manifest.layer_reader(name).read(ctx) for name in LAYER}
    gaps = client_gaps_ms(ctx)
    hops = {"client": _mean(gaps),
            "gateway": readers.gateway_summary_mean_ms(ctx, GATEWAY_GAP),
            "runner": readers.engine_phase_mean_ms(ctx, "runner_gap"),
            "engine": layer["engine_tpot_ms"]}
    names = list(hops)
    engine_gap = stall.per_token_ms(ctx, "gap_lane_period_s")
    a, b = (ctx[k].get("host_phase_s") or {} for k in ("health0", "health1"))
    judged = metrics.latency(ctx["records"], "tpot", 50)
    return {
        "cell": ctx["cell"], "client_tpot_p50_ms": judged["value"],
        "waterfall_ms": hops,
        "hops_ms": {f"{outer}_less_{inner}": _less(hops[outer], hops[inner])
                    for outer, inner in zip(names, names[1:])},
        "engine_gap_ms": engine_gap,
        "decode_ms": _less(engine_gap, layer["tpot_admit_stall_ms"]),
        "admit_episode_ms": stall.per_token_ms(ctx, "gap_lane_admit_s"),
        **{k: layer[k] for k in LAYER if k != "engine_tpot_ms"},
        "engine_within_pct_of_client":
            100.0 * (hops["engine"] / hops["client"] - 1.0)
            if hops["engine"] and hops["client"] else None,
        "lanes": {k: readers.counter_delta(ctx, f"gap_{k}")
                  for k in ("tokens", "lane_steps", "clean_lane_steps")},
        "admission": {
            "host_phases_s": sum(b.get(k, 0.0) - a.get(k, 0.0)
                                 for k in ADMIT_PHASES) if b else None,
            "admissions": readers.counter_delta(ctx, "gap_admissions"),
            "dispatches": readers.counter_delta(ctx, "admit_dispatches"),
            "tokens": readers.counter_delta(ctx, "admit_tokens"),
            "tokens_padded": readers.counter_delta(ctx,
                                                   "admit_tokens_padded")},
        "observations": {
            "records_with_a_gap": len(gaps),
            GATEWAY_GAP: gw.observations(ctx, GATEWAY_GAP),
            **{f"latency.{part}":
               readers.nested_delta(ctx, "latency", f"{part}_count")
               for part in ("runner_gap", "tpot", "gap_max")}},
        "max_batch": ctx["engine"]["max_batch"],
    }


# -- the trace's side ---------------------------------------------------------

def read_trace(path: str) -> dict:
    """``host_phases.pick``'s three — the serve loop's phase line, chip 0's
    operations and program runs — and ``stats``: the phase line once more,
    each event with its stats. One pass: a plane's lines are read once."""
    import re

    from jax.profiler import ProfileData
    out = {"phases": None, "stats": [], "ops": [], "modules": []}
    chip = None
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:(?:TPU|GPU):(\d+)$", plane.name)
        if m and (chip is None or int(m.group(1)) < chip):
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                    key = "ops" if line.name == trace.OPS_LINE else "modules"
                    out[key] = [(ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)) for ev in line.events]
        elif plane.name.startswith("/host:") and out["phases"] is None:
            for line in plane.lines:
                events = [(ev.name, float(ev.start_ns),
                           float(ev.duration_ns), dict(ev.stats))
                          for ev in line.events
                          if host_phases.PHASE.match(ev.name)]
                if any(e[0] == host_phases.MARKER for e in events):
                    out["stats"] = events
                    out["phases"] = [e[:3] for e in events]
                    break
    return out


def episodes(phases: list) -> list:
    """``[(start, end)]`` of the admission episodes on the phase line: from
    the first ``engine.admit`` after the episode before to the end of the
    next ``engine.deliver_first`` — where the engine's admission clock runs."""
    out, start = [], None
    for name, a, d in sorted(phases, key=lambda e: e[1]):
        if name == ADMIT and start is None:
            start = a
        elif name == DELIVER and start is not None:
            out.append((start, a + d))
            start = None
    return out


def _inside(intervals: list, lo: float, hi: float) -> float:
    return sum(max(min(b, hi) - max(a, lo), 0.0) for a, b in intervals)


def check(data: dict, decode_step_ms) -> dict:
    """See the module's text. Times in ms; ``covered_pct`` is the three
    named parts of chip 0's time inside the episodes over the fan-outs'
    admit parts."""
    idle, first, last = host_phases.idle_intervals(data["ops"])
    fans = [(a, st) for name, a, _, st in data["stats"]
            if name == FANOUT and st.get("lanes") and "period_us" in st
            and a - st["period_us"] * 1e3 >= first and a <= last]
    if not fans:
        return {"fanouts": 0}
    lo = min(a - st["period_us"] * 1e3 for a, st in fans)
    hi = max(a for a, _ in fans)
    eps = [(a, b) for a, b in episodes(data["phases"]) if a >= lo and b <= hi]
    runs = {"prefill": [], "decode": [], "other": []}
    for name, a, d in data["modules"]:
        key = trace.program_key(name)
        kind = "prefill" if key in ADMIT_PROGRAMS else \
            "decode" if key.endswith(("decode", "verify")) else "other"
        runs[kind].append((a, a + d))
    segments = host_phases.innermost(data["phases"])
    device = dict.fromkeys(("prefill", "decode", "other", "idle_admit",
                            "idle_other"), 0.0)
    for lo_e, hi_e in eps:
        for kind, spans in runs.items():
            device[kind] += _inside(spans, lo_e, hi_e)
        inside = [(max(a, lo_e), min(b, hi_e)) for a, b in idle
                  if a < hi_e and b > lo_e]
        for name, ns in host_phases.charge(inside, segments).items():
            device["idle_admit" if name in ADMIT_PHASES
                   else "idle_other"] += ns
    admit_ms = sum(st["admit_us"] for _, st in fans) / 1e3
    named = (device["prefill"] + device["decode"] + device["idle_admit"]) / 1e6
    # the decode runs between the first period's start and the last
    # fan-out: their durations summed, and the time at least one covers —
    # where a run's event opens before the run before it has ended, the
    # sum (what ``decode_step_ms`` divides) passes what the streams saw
    decode = [(a, b - a) for a, b in runs["decode"] if a >= lo and b <= hi]
    clean = [st for _, st in fans if st.get("clean")]
    steps = sum(st["k"] for st in clean)
    period = sum(st["period_us"] for st in clean) / 1e3 / steps \
        if steps else None
    # the other fan-outs: what their periods hold beyond the clean step for
    # each of their steps, against the episodes' chip time that is no decode
    touched = [st for _, st in fans if not st.get("clean")]
    stall_ms = sum(st["period_us"] for st in touched) / 1e3 \
        - sum(st["k"] for st in touched) * period if steps else None
    not_decode = (device["prefill"] + device["other"] + device["idle_admit"]
                  + device["idle_other"]) / 1e6
    return {
        "fanouts": len(fans), "span_ms": (hi - lo) / 1e6,
        "period_ms": sum(st["period_us"] for _, st in fans) / 1e3,
        "admit_ms": admit_ms, "episodes": len(eps),
        "episodes_ms": sum(b - a for a, b in eps) / 1e6,
        "device_in_episodes_ms": {k: v / 1e6 for k, v in device.items()},
        "covered_pct": 100.0 * named / admit_ms if admit_ms else None,
        "stall_ms": stall_ms, "episodes_not_decode_ms": not_decode,
        "stall_covered_pct": 100.0 * not_decode / stall_ms
        if stall_ms else None,
        "clean_fanouts": len(clean), "clean_steps": steps,
        "clean_period_per_step_ms": period,
        "decode_step_ms": decode_step_ms,
        "decode_runs": {"runs": len(decode),
                        "sum_ms": sum(d for _, d in decode) / 1e6,
                        "union_ms": trace.union_ns(decode) / 1e6},
        "period_over_step_pct": 100.0 * (period / decode_step_ms - 1.0)
        if period and decode_step_ms else None,
    }


def decode_step_ms(run_dir: str):
    """The traced run's own ``decode_step_ms``, from the line it printed."""
    try:
        with open(os.path.join(run_dir, "result.json")) as f:
            return json.load(f)["metrics"]["decode_step_ms"]["value"]
    except (OSError, KeyError, ValueError):
        return None


def describe(run_dir: str) -> dict:
    with open(os.path.join(run_dir, "context.json")) as f:
        ctx = json.load(f)
    out = {"run": os.path.basename(run_dir), **waterfall(ctx)}
    path = trace.find_xplane(os.path.join(run_dir, "trace"))
    if path:
        data = read_trace(path)
        out["check"] = check(data, decode_step_ms(run_dir)) \
            if data["phases"] and data["ops"] else {"fanouts": 0}
    return out


def main() -> int:
    dirs = sys.argv[1:] or sorted(
        glob.glob(os.path.join(ROOT, "benchmark", "out", "*.trace1")),
        key=os.path.getmtime)[-1:]
    for run_dir in dirs:
        print(json.dumps(describe(run_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
