"""engine: host milliseconds the serve loop's thread spent per decode window
it processed, over the window: the self time of every phase in
``engine.stats()`` ``host_phase_s`` except those in which the host only waits
(``engine.window.sync``, ``engine.first_sync``, ``engine.park``), divided by
``windows_processed``. The same milliseconds whatever the model's depth."""
from benchmark import host_phases, readers


def read(ctx):
    a, b = (ctx[k].get("host_phase_s") for k in ("health0", "health1"))
    windows = readers.counter_delta(ctx, "windows_processed")
    if a is None or b is None or not windows:
        return None
    waits = host_phases.GROUPS["blocked"]
    busy = sum(v - a.get(k, 0.0) for k, v in b.items() if k not in waits)
    return busy / windows * 1e3
