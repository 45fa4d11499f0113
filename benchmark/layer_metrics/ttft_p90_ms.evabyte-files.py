"""client: ``ttft_p90_ms`` of this cell — 90th percentile of due -> first
streamed token over the judged requests. Not an end-to-end metric here: a
first token waits for the prefill of 4-16 k positions (2-8 windows, one
admission group each) behind whatever admission is already running, so it
follows the order of the arrivals more than the program. Read in the traced
run, so with the profiler's overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
