"""worker, runner: share of the traced span in which chip 0 was idle while the
serve loop had yielded to the rest of the runner's event loop —
``engine.yield`` (SSE writes, request handlers) or ``runner.heartbeat`` —
in %."""
from benchmark import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "eventloop")
