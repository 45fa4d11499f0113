"""engine: mean number of sequences in a decode step: tokens the decode
steps produced (all tokens less one first token per request, which prefill
produces) over decode steps, from ``engine.stats()`` deltas."""
from benchmark import readers


def read(ctx):
    return readers.decode_batch(ctx)
