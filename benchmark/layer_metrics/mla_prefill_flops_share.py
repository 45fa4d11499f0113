"""kernels: share of peak bf16 FLOP/s that the FLOPs latent attention's
prefill NEEDS (the family's ``kernel_cost`` of the blocked prefill: a cache
row's keys and values made from its latent once a dispatch, a score and a
weighted value for every (query, row) pair the causal mask lets through,
every layer) reach in the device time that the operations under latent
attention's scopes (``MLA_SCOPES``: the weights' layout and the kernel) TOOK
inside the chunk and group programs' runs. The rows and pairs are the
engine's own counts (``prefill_rows_attended``, ``prefill_pairs_attended``),
a chunk's mean over the window times the chunks of the traced runs
(``readers.prefill_time_and_tokens``: a run the trace cut is not among
them). What the dispatches need, not what the kernel does: it makes a row's
keys and values once a query tile and multiplies the block the diagonal
crosses whole, so it cannot pass 100 %. Left out where the engine has no such
counters, the family no such kernel, or the trace no prefill run."""
from benchmark import host_phases, peaks, readers, scope_events

PROGRAMS = ("chunk_", "chunkgroup_")


def read(ctx):
    family = ctx["family"]
    kernel = getattr(family, "PREFILL_KERNEL", None)
    rows = readers.counter_delta(ctx, "prefill_rows_attended")
    pairs = readers.counter_delta(ctx, "prefill_pairs_attended")
    chunks = readers.counter_delta(ctx, "admit_chunks")
    _, tokens = readers.prefill_time_and_tokens(ctx)
    path = (ctx.get("trace") or {}).get("file")
    maps = (ctx.get("health_ready") or {}).get("device_scopes")
    if not kernel or not rows or not pairs or not chunks or not tokens \
            or not path or not maps:
        return None
    maps = {k: v for k, v in maps.items() if k.startswith(PROGRAMS)}
    data = host_phases.load(path)
    took = scope_events.under(data["ops"], data["modules"], maps,
                              family.MLA_SCOPES).get("seconds")
    if not took:
        return None
    traced = tokens / ctx["engine"]["prefill_chunk"] / chunks
    cost = family.kernel_cost(kernel, ctx["model"], ctx["engine"],
                              pairs * traced, rows * traced)
    peak = peaks.chip_peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * cost["flops"] / took / (peak * ctx["chips"])
