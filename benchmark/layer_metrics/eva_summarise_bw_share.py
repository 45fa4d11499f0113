"""kernels: share of the chip's HBM bandwidth that the bytes the windows
closed inside the trace NEED (the family's ``kernel_cost`` of its summarise:
a window's keys and values read once in every layer, a page of summaries
written) would take in the device time that the operations under the
summarise's scope TOOK, in the decode, chunk and group programs alike. The
trips of the summarise loop are counted in the trace (``scope_events``): one
a closed window and layer. What the windows need, not what the operations
move: the gather of the window's pages, the float32 copies and the scores
are the program's own affair. Left out where no window closed inside the
trace, or the program has no such scope."""
from benchmark import peaks, scope_events


def read(ctx):
    family = ctx["family"]
    scopes = getattr(family, "EVA_SCOPES", ())
    got = scope_events.read(ctx, scopes)
    if not got or not got["trips"] or not got["seconds"]:
        return None
    cost = family.kernel_cost(scopes[0], ctx["model"], ctx["engine"], 0, 0)
    if not cost:
        return None
    windows = got["trips"] / family.marker_calls_per_step(ctx["model"])
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * windows * cost["bytes"] / ctx["chips"] / got["seconds"] \
        / peak
