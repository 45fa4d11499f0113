"""KV pool: of the prompt rows the window's admissions took, the share a
prefix hit served from pages already written — with the conv layers' tails
restored from those pages, which is what lets a hit stand beside that state
at all (``prefix_rows_reused`` over ``prompt_rows_admitted``, the engine's
counters, as the delta over the window), in %. The accepted
``prefix_rows_reused_share``'s arithmetic on this family's cell; an engine
that restored no tail in the window (``conv_tail_restores``: absent, or
unmoved) kept no such state beside its cache, and the metric is left out."""
from benchmark import manifest, readers


def read(ctx):
    if not readers.counter_delta(ctx, "conv_tail_restores"):
        return None
    return manifest.layer_reader("prefix_rows_reused_share").read(ctx)
