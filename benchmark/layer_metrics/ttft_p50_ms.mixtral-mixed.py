"""client: ``ttft_p50_ms`` of this cell — median/percentile of due -> first
streamed token over the judged requests. Not an end-to-end metric here: in two
sets of six runs its spread was 7-14 % (PERF.md, Findings), more than a bound
of 10 % can carry. Read in the traced run, so with the profiler's overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
