"""device: share of the traced span in which chip 0 was idle under no phase of
the serve loop at all — the instrumentation's own blind spot — in %. With
the four named shares it adds up to chip 0's idle share."""
from benchmark import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, host_phases.UNNAMED)
