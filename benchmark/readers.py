"""What the per-layer readers share: deltas of the runner's and the
gateway's cumulative counters over the window. A reader gets the context
``run.layer_context`` builds and returns a number, or None when what it reads
is not there (the harness then leaves the metric out of the line)."""

from __future__ import annotations


def counter_delta(ctx: dict, key: str):
    a, b = ctx["health0"].get(key), ctx["health1"].get(key)
    if a is None or b is None:
        return None
    return b - a


def nested_delta(ctx: dict, group: str, key: str):
    a = (ctx["health0"].get(group) or {}).get(key)
    b = (ctx["health1"].get(group) or {}).get(key)
    if a is None or b is None:
        return None
    return b - a


def decode_batch(ctx: dict):
    """Mean number of sequences in a decode step: tokens the decode steps
    produced (all tokens less one first token per request, which prefill
    produces) over decode steps, from ``engine.stats()`` deltas."""
    tokens = counter_delta(ctx, "tokens_generated")
    steps = counter_delta(ctx, "decode_steps")
    firsts = nested_delta(ctx, "latency", "ttft_count")
    if not steps or tokens is None:
        return None
    return (tokens - (firsts or 0)) / steps


def engine_phase_mean_ms(ctx: dict, phase: str):
    """Mean of one engine latency phase (``queue_wait``, ``ttft``, ...) over
    the requests of the window: the summaries are cumulative mean and count,
    so the window's mean is the difference of their products."""
    a = ctx["health0"].get("latency") or {}
    b = ctx["health1"].get("latency") or {}
    n = b.get(f"{phase}_count", 0) - a.get(f"{phase}_count", 0)
    if n <= 0:
        return None
    total = b[f"{phase}_mean_s"] * b[f"{phase}_count"] \
        - a.get(f"{phase}_mean_s", 0.0) * a.get(f"{phase}_count", 0)
    return total / n * 1e3


def gateway_summary_mean_ms(ctx: dict, name: str):
    """The same, for every labelled series of one gateway summary."""
    def totals(snap):
        n = total = 0.0
        for key, s in (snap.get("summaries") or {}).items():
            if key == name or key.startswith(name + "{"):
                n += s["count"]
                total += s["mean"] * s["count"]
        return n, total

    n0, t0 = totals(ctx["gateway0"])
    n1, t1 = totals(ctx["gateway1"])
    if n1 - n0 <= 0:
        return None
    return (t1 - t0) / (n1 - n0) * 1e3


def coldstart(ctx: dict, key: str):
    return ctx["health_ready"].get(f"coldstart_{key}")


PREFILL_PROGRAMS = ("jit_chunk", "jit_group", "jit_traced_splice", "jit_gather")


def prefill_time_and_tokens(ctx: dict):
    """(device seconds of the chunked-prefill programs in the trace, prompt
    tokens they processed, counted as the program counts them: whole chunks;
    a fused group holds ``admit_group_chunks``)."""
    seconds, hit = program_seconds(ctx, PREFILL_PROGRAMS)
    if not hit:
        return None, 0
    knobs = ctx["engine"]
    chunks = hit.get("jit_chunk", {}).get("runs", 0) \
        + knobs["admit_group_chunks"] * hit.get("jit_group", {}).get("runs", 0)
    return seconds, chunks * knobs["prefill_chunk"]


def program_seconds(ctx: dict, names: tuple):
    """(seconds, runs by program) of the traced programs called ``names``."""
    programs = (ctx["trace"] or {}).get("programs") or {}
    hit = {k: v for k, v in programs.items() if k in names}
    if not hit:
        return None, {}
    return sum(v["seconds"] for v in hit.values()), hit


def kernel_seconds_per_step(ctx: dict, kernel: str,
                            program: str = "jit_decode"):
    """Device seconds per decode step, per chip, of the operations the trace
    prints as ``kernel`` inside the runs of ``program``; None where the
    trace holds no such operation or no counted step."""
    trace = ctx["trace"] or {}
    steps = ((trace.get("programs") or {}).get(program) or {}).get("steps")
    seconds = sum(v for k, v in (trace.get("op_seconds") or {}).items()
                  if k.split(":", 1)[0] == f"{program}/{kernel}")
    if not steps or not seconds:
        return None
    return seconds / steps


def demoted_latency(ctx: dict, reader_file: str):
    """For a reader named ``<metric>.<cell>.py``: an end-to-end latency that
    did not repeat well enough in that cell to carry a bound, read in the
    traced run as a per-layer metric of the client. ``<metric>`` is one of
    ``metrics.end_to_end``'s names; the same arithmetic, over the judged
    requests of the window."""
    import os

    from benchmark import metrics
    name = os.path.basename(reader_file).split(".", 1)[0]
    return metrics.end_to_end(name, ctx["records"], ctx["seconds"], 0.0)
