"""engine: the engine's own time per output token — a request's last
delivery less its first token's, over its tokens less one, told once a
request where the window that retired it is fanned out (``latency.tpot``,
the summary ``tpu9_engine_tpot_s``) — as the mean over the requests that
retired in the window. The client's ``tpot``, less the relay
(``tpot_relay_ms``). None on a program that tells no such summary."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "tpot")
