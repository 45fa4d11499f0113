"""KV pool: of the prompt rows admitted in the window, the share a prefix hit
served from pages that were already written (``prefix_rows_reused`` over
``prompt_rows_admitted``, the engine's counters, as the delta over the
window), in %. A session's turn sends its whole history: all of it but the
suffix is reused, or the turn prefills tens of thousands of rows again. An
engine without the counters: left out."""
from benchmark import readers


def read(ctx):
    reused = readers.counter_delta(ctx, "prefix_rows_reused")
    admitted = readers.counter_delta(ctx, "prompt_rows_admitted")
    if reused is None or not admitted:
        return None
    return 100.0 * reused / admitted
