"""engine: compilations inside the window (``graph_compiles_post_warmup``).
Anything but 0 also makes the run incorrect."""
from benchmark import readers


def read(ctx):
    return readers.counter_delta(ctx, "graph_compiles_post_warmup")
