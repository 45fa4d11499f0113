"""gateway, router: mean time the first token took from the runner's write
through the gateway's ``SseParser`` to the client's socket: the gateway's
``tpu9_gateway_stream_first_s`` (headers back -> first token written) less
the runner's ``latency.runner_first`` (headers written -> first token
written). Both intervals open with the headers, so this is the token's way
less the headers' way back, which ``runner_door_ms`` holds. None where the
two summaries are not of the same requests."""
from benchmark import manifest, readers


def read(ctx):
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    first = readers.gateway_summary_mean_ms(ctx, gw.FIRST)
    runner = readers.engine_phase_mean_ms(ctx, "runner_first")
    if first is None or runner is None or not gw.same_requests(
            ctx, gw.observations(ctx, gw.FIRST),
            readers.nested_delta(ctx, "latency", "runner_first_count")):
        return None
    return first - runner
