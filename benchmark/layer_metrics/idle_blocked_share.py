"""engine: share of the traced span in which chip 0 was idle while the host
itself waited: under ``engine.window.sync`` or ``engine.first_sync`` (the
result transfer; a window too short to hide the fan-out) or ``engine.park``
(no work), in %."""
from benchmark import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "blocked")
