"""gateway, router: mean time a request waited in the fleet router's queue,
from the gateway's ``tpu9_router_queue_wait_s`` summaries, as the delta over
the window."""
from benchmark import readers


def read(ctx):
    return readers.gateway_summary_mean_ms(ctx, "tpu9_router_queue_wait_s")
