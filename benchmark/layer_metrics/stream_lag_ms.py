"""worker, runner: mean lag between the engine putting a request's first token
on its queue and the runner's handler having written it to the client
(``stream_lag``): the wait for the event loop the serve loop shares."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "stream_lag")
