"""kernels: share of the chip's HBM bandwidth that the bytes a decode step
NEEDS (the family's ``decode_bytes_per_step``: weights a token of the batch
passes through, keys and values of the resident context; per chip) would take
in the time a decode step TOOK (``decode_step_ms``, device trace)."""
from benchmark import metrics, peaks, readers


def read(ctx):
    step_ms = (ctx["trace"] or {}).get("decode_step_ms")
    tokens = readers.counter_delta(ctx, "tokens_generated")
    steps = readers.counter_delta(ctx, "decode_steps")
    if not step_ms or not steps or tokens is None:
        return None
    batch = tokens / steps
    context = metrics.mean_resident_context(ctx["records"], ctx["seconds"])
    need = ctx["family"].decode_bytes_per_step(
        ctx["model"], batch, context) / ctx["chips"]
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * need / (step_ms / 1e3) / peak
