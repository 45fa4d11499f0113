"""model step: median device time of one decode step: each run of the decode
program in the trace, divided by the steps it held (its paged-attention
kernel calls over the layers)."""


def read(ctx):
    return (ctx["trace"] or {}).get("decode_step_ms")
