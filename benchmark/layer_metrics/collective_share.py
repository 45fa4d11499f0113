"""sharding: device time of the collective operations (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute) over the device's
busy time, per chip, in %."""


def read(ctx):
    trace = ctx["trace"] or {}
    if not trace.get("busy_s"):
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]
