"""engine: mean decode steps of a window the serve loop processed, over the
window: ``decode_steps`` over ``windows_processed`` from ``engine.stats()``
deltas (a speculative verify window counts as a window and adds no decode
step; no cell runs one). What the serve loop pays once a window — the sync,
the fan-out, the dispatch, a pass of the event loop — it pays this many
times less a token the higher it reads; the most it can read is the largest
of the configuration's ``decode_steps``."""
from benchmark import readers


def read(ctx):
    steps = readers.counter_delta(ctx, "decode_steps")
    windows = readers.counter_delta(ctx, "windows_processed")
    if steps is None or not windows:
        return None
    return steps / windows
