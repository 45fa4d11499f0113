"""engine: the share of the tokens the chunked-prefill programs ran over
that were padding: 100 x (1 - ``admit_tokens`` / ``admit_tokens_padded``),
the suffixes' real tokens behind their prefix hits over their chunks taken
whole, as the program counts both where it dispatches them. Deltas over the
window; None on a program without the counters, or where nothing was
admitted."""
from benchmark import readers


def read(ctx):
    real = readers.counter_delta(ctx, "admit_tokens")
    padded = readers.counter_delta(ctx, "admit_tokens_padded")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
