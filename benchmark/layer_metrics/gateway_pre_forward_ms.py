"""gateway, router: mean time of a streamed request from the first gateway
code that sees it to its being sent to the runner (``gateway.pre_forward``:
auth, the deployment and stub lookups, the body read and its parses, the
journal gate, ``admit_stream``, the wait for a container's token), from the
gateway's ``tpu9_gateway_stream_pre_s`` summary as the delta over the window.

The three ``tpu9_gateway_stream_*_s`` summaries carry no labels and hold one
observation a streamed request; ``observations`` counts one of them over the
window, for the readers that subtract a runner's or the client's number from
the gateway's and must know that both are of the same requests."""
from benchmark import readers

PRE = "tpu9_gateway_stream_pre_s"
CONNECT = "tpu9_gateway_stream_connect_s"
FIRST = "tpu9_gateway_stream_first_s"


def observations(ctx, summary):
    def count(snap):
        return (snap.get("summaries") or {}).get(summary, {}).get("count", 0)
    return count(ctx["gateway1"]) - count(ctx["gateway0"])


def same_requests(ctx, *counts):
    """Whether the counts are of one set of requests, give or take those in
    flight at either end of the window: a batch of them at most."""
    return None not in counts and min(counts) > 0 and \
        max(counts) - min(counts) <= ctx["engine"]["max_batch"]


def read(ctx):
    return readers.gateway_summary_mean_ms(ctx, PRE)
