"""engine: mean wait between a request reaching the engine and its admission
(``engine.stats()`` ``queue_wait``), over the requests of the window."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "queue_wait")
