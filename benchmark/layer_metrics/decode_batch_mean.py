"""engine: mean number of sequences in a decode step: tokens the decode
steps produced (all tokens less one first token per request, which prefill
produces) over decode steps, from ``engine.stats()`` deltas."""
from benchmark import readers


def read(ctx):
    tokens = readers.counter_delta(ctx, "tokens_generated")
    steps = readers.counter_delta(ctx, "decode_steps")
    firsts = readers.nested_delta(ctx, "latency", "ttft_count")
    if not steps or tokens is None:
        return None
    return (tokens - (firsts or 0)) / steps
