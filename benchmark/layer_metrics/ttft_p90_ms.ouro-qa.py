"""client: ``ttft_p90_ms`` of this cell — 90th percentile of due -> first
streamed token over the judged requests. Not an end-to-end metric here: a
first token waits for a free slot AND for its worst-case reservation in a
pool of a few dozen blocks, so it swings with the order of the arrivals
(PERF.md, Findings). Read in the traced run, so with the profiler's
overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
