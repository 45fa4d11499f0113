"""client: ``ttft_p50_ms`` of this cell — median of due -> first streamed
token over the judged requests. Not an end-to-end metric here: a closed loop
of sixteen sessions, where a turn's first token waits for the prefill of a
256-767 token suffix against tens of thousands of cached rows, behind
whatever admission is running. Read in the traced run, so with the
profiler's overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
