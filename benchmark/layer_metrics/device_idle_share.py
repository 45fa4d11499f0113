"""device: 1 - (union of the op intervals / traced span), mean over the
chips, in %. Fewer layers than a deployment make the host's share larger."""


def read(ctx):
    trace = ctx["trace"] or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
