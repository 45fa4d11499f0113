"""engine: share of the traced span in which chip 0 was idle under the serve
loop's own work on a window — ``engine.window.dispatch``,
``engine.window.fanout`` (token delivery, retirement) or
``engine.kvtier_tick`` — in %."""
from benchmark import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "window")
