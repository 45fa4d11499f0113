"""KV pool: cache entries a resident token costs, over the decode steps of
the window — the engine's ``decode_resident_entries`` over its
``decode_resident_tokens`` (each summed over the lanes of every decode step
dispatched), as the delta over the window. A cache of window summaries holds
one entry for every ``chunk_size`` tokens of a closed window and reads about
``1 / chunk_size + (1 - 1 / chunk_size) W / 2 n`` for sequences of ``n``
tokens; a cache of one row a token would read 1.0, and its engine has
neither counter: the metric is then left out."""
from benchmark import readers


def read(ctx):
    entries = readers.counter_delta(ctx, "decode_resident_entries")
    tokens = readers.counter_delta(ctx, "decode_resident_tokens")
    if entries is None or not tokens:
        return None
    return entries / tokens
