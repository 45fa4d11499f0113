"""worker, runner: mean time a streamed request spent between the gateway's
send and the first line of the runner's handler, and its response's headers
on the way back: the gateway's ``tpu9_gateway_stream_connect_s`` (send ->
headers back) less the runner's ``latency.ingest`` (handler's first line ->
headers written), each on its own process's clock. Transport, and the wait
for the runner's event loop, which the serve loop holds for a window at a
time. None where the two summaries are not of the same requests."""
from benchmark import manifest, readers


def read(ctx):
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    connect = readers.gateway_summary_mean_ms(ctx, gw.CONNECT)
    ingest = readers.engine_phase_mean_ms(ctx, "ingest")
    if connect is None or ingest is None or not gw.same_requests(
            ctx, gw.observations(ctx, gw.CONNECT),
            readers.nested_delta(ctx, "latency", "ingest_count")):
        return None
    return connect - ingest
