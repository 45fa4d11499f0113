"""client: ``ttft_p50_ms`` of this cell — median of due -> first streamed
token over the judged requests. Not an end-to-end metric here: a closed loop
of twenty-four sessions, where a turn's first token waits for the gather of
8-32 k cached rows, the restore of eleven tails and the prefill of a 256-383
token suffix, behind whatever admission is running. Read in the traced run,
so with the profiler's overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
