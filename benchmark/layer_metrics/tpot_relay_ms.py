"""gateway, router: what the relay adds to a token's gap — the gateway's own
time per output token (``tpu9_gateway_stream_gap_s``: first token written to
the client -> last, over the tokens less one, once a stream's first attempt)
less the engine's (``engine_tpot_ms``): the runner's handler and its event
loop, the transport, the gateway's ``SseParser`` and its writes. The runner's
own (``latency.runner_gap``) splits it (``tools/tpot.py``). May read below 0,
as ``gateway_first_relay_ms`` may. A difference of two means of hundredths
of a millisecond, so it is read only where the three hops' summaries are of
one set of streams: the gateway's, the runner's and the engine's counts
within ``SAME_STREAMS`` of the smallest. A loop cut at the window's end
leaves its running streams in the engine's summary alone (``mixtral-batch``:
255 / 224 / 256; ``kimi-docs``: 213 / 213 / 229, a session each), and on
``ling-reason`` the harness's ``/health`` polls wait behind the streams, so
the three windows are not one (260 / 170 / 267): None there. The metric's
``workloads`` are the cells whose every stream ends inside the snapshots —
the open loops, which drain, and ``mistral-tp4-long``, whose sessions end
their turns — and not those three."""
from benchmark import manifest, readers

GAP = "tpu9_gateway_stream_gap_s"
SAME_STREAMS = 0.1


def same_streams(*counts):
    return None not in counts and min(counts) > 0 and \
        max(counts) - min(counts) <= SAME_STREAMS * min(counts)


def read(ctx):
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    gap = readers.gateway_summary_mean_ms(ctx, GAP)
    engine = readers.engine_phase_mean_ms(ctx, "tpot")
    if gap is None or engine is None or not same_streams(
            gw.observations(ctx, GAP),
            readers.nested_delta(ctx, "latency", "runner_gap_count"),
            readers.nested_delta(ctx, "latency", "tpot_count")):
        return None
    return gap - engine
