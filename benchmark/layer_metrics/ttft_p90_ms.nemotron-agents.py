"""client: ``ttft_p90_ms`` of this cell — 90th percentile of due -> first streamed
token over the judged requests. Not an end-to-end metric here: the
benchmark's end-to-end metrics hold no TTFT; a first token waits for the
prefill of a 256-2,048 token prompt (up to four chunks of 512 through the
mixers' scan and the sorted expert layers) behind whatever admission is
running and the decode window in flight. Read in the traced run, so with the
profiler's overhead."""
from benchmark import readers


def read(ctx):
    return readers.demoted_latency(ctx, __file__)
