"""engine: share of the traced span in which chip 0 was idle while the serve
loop was admitting: under ``engine.admit`` and its parts (lookup, plan,
dispatch, finish) or ``engine.deliver_first``, in %."""
from benchmark import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "admit")
